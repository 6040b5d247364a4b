"""Synthetic unstructured FE-style SPD operator of poisson3Db's profile.

A frozen copy of ``amgcl_tpu.ops.unstructured.fe_like_problem``: random
points in a unit cube, a k-nearest-neighbour graph, edge weights that
scale like a FE stiffness entry (1/h^2, with the distance floored at a
fifth of the median), symmetrised, as a graph Laplacian plus a small mass
term. The matrix is fixed by the configuration's ``matrix_seed``; the
benchmark's ``--seed`` draws only the right-hand sides.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.spatial import cKDTree


def build(cfg):
    """The configuration's operator as a float64 scipy CSR matrix with
    sorted indices. Reads ``cfg["rows"]``, ``cfg["nnz_target"]`` and
    ``cfg["matrix_seed"]``."""
    n = int(cfg["rows"])
    rng = np.random.RandomState(int(cfg["matrix_seed"]))
    pts = rng.rand(n, 3)
    k = max(int(round(int(cfg["nnz_target"]) / n)) - 1, 4)
    dist, idx = cKDTree(pts).query(pts, k=k + 1)
    rows = np.repeat(np.arange(n), k)
    cols = idx[:, 1:].reshape(-1)
    d = dist[:, 1:].reshape(-1)
    d = np.maximum(d, 0.2 * np.median(d))
    d2 = d * d
    w = (1.0 / d2) * (0.9 + 0.2 * rng.rand(len(rows)))
    w *= np.mean(d2)
    G = sp.coo_matrix((w, (rows, cols)), shape=(n, n))
    G = (G + G.T) * 0.5
    L = sp.diags(np.asarray(G.sum(axis=1)).ravel() + 0.01) - G
    A = L.tocsr()
    A.sort_indices()
    return A
