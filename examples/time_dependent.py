"""Fast hierarchy rebuild for time-dependent problems — the reference's
allow_rebuild workflow (amg.hpp:229-269)."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from amgcl_tpu import make_solver, AMGParams, CSR
from amgcl_tpu.solver.cg import CG
from amgcl_tpu.utils.sample_problem import poisson3d

A, rhs = poisson3d(32)
solve = make_solver(A, AMGParams(dtype=jnp.float64), CG(tol=1e-8))
x, info = solve(rhs)
print("step 0: %d iterations" % info.iters)

for step in range(1, 4):
    # values drift; structure fixed -> transfer operators reused
    A_t = CSR(A.ptr.copy(), A.col.copy(), A.val * (1 + 0.05 * step), A.ncols)
    t0 = time.perf_counter()
    solve.rebuild(A_t)
    dt = time.perf_counter() - t0
    x, info = solve(rhs, x0=x)
    print("step %d: rebuild %.3fs, %d iterations" % (step, dt, info.iters))
