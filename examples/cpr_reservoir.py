"""CPR preconditioning of a reservoir-style block system — the reference's
examples/cpr.cpp."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import numpy as np
import scipy.sparse as sp
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from amgcl_tpu import make_solver, AMGParams, CSR
from amgcl_tpu.models.cpr import CPR
from amgcl_tpu.solver.bicgstab import BiCGStab
from amgcl_tpu.utils.sample_problem import poisson3d

b = 3
Ap, _ = poisson3d(10)
nc = Ap.nrows
K = sp.kron(Ap.to_scipy(), np.eye(b)).tocsr()
rows = np.concatenate([np.arange(nc) * b + k for k in range(1, b)])
K = (K + sp.csr_matrix((np.full(len(rows), 0.3), (rows, (rows // b) * b)),
                       shape=K.shape)
     + sp.csr_matrix((np.full(len(rows), float(nc)), (rows, rows)),
                     shape=K.shape)).tocsr()
A = CSR.from_scipy(K).to_block(b)
rhs = np.ones(nc * b)

precond = CPR(A, pressure_prm=AMGParams(dtype=jnp.float64),
              dtype=jnp.float64)
solve = make_solver(A, precond, BiCGStab(maxiter=200, tol=1e-8))
x, info = solve(rhs)
print(precond)
print("Iterations: %d, error %.2e" % (info.iters, info.resid))
