"""3D Poisson, 7-point finite differences on an n x n x n grid.

A frozen copy of the operator that ``amgcl_tpu.utils.sample_problem.
poisson3d(n)`` builds (cubic grid, isotropic), so that no change to the
program can change the matrix this benchmark solves. Dirichlet boundaries
are folded into the operator and the stencil is scaled by (n - 1)^2.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def build(cfg):
    """The configuration's operator as a float64 scipy CSR matrix with
    sorted indices. Reads ``cfg["n"]``, the points per grid side."""
    n = int(cfg["n"])
    h2i = float(n - 1) ** 2 if n > 1 else 1.0
    e = np.ones(n)
    T = sp.diags([-e[:-1], 2 * e, -e[:-1]], [-1, 0, 1], format="csr")
    I = sp.identity(n, format="csr")
    Axy = sp.kron(I, sp.kron(I, T)) + sp.kron(I, sp.kron(T, I))
    Az = sp.kron(T, sp.kron(I, I))
    A = sp.csr_matrix(((Axy + Az) * h2i).astype(np.float64))
    A.sort_indices()
    return A
