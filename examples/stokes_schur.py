"""Schur pressure correction on a Stokes-type saddle point — the
reference's examples/schur_pressure_correction.cpp / Stokes tutorial."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import numpy as np
import scipy.sparse as sp
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from amgcl_tpu import make_solver, AMGParams
from amgcl_tpu.models.schur import SchurPressureCorrection
from amgcl_tpu.solver.gmres import FGMRES


def stokes(n):
    T = sp.diags([-np.ones(n - 1), 2 * np.ones(n), -np.ones(n - 1)],
                 [-1, 0, 1])
    L = (sp.kron(sp.identity(n), T) + sp.kron(T, sp.identity(n))).tocsr()
    nu = L.shape[0]
    A = sp.block_diag([L, L]).tocsr()
    D = sp.diags([-np.ones(nu - 1), np.ones(nu)], [-1, 0], shape=(nu, nu))
    B = sp.hstack([D, 0.5 * D]).tocsr()
    K = sp.bmat([[A, B.T], [B, -1e-2 * sp.identity(nu)]]).tocsr()
    pmask = np.zeros(K.shape[0], dtype=bool)
    pmask[2 * nu:] = True
    return K, pmask


K, pmask = stokes(24)
rhs = np.ones(K.shape[0])
precond = SchurPressureCorrection(
    K, pmask,
    usolver_prm=AMGParams(dtype=jnp.float64),
    psolver_prm=AMGParams(dtype=jnp.float64),
    dtype=jnp.float64)
solve = make_solver(K, precond, FGMRES(maxiter=300, tol=1e-8))
x, info = solve(rhs)
print(precond)
print("Iterations: %d, error %.2e" % (info.iters, info.resid))
