"""float32 hierarchy inside a float64 Krylov loop — the reference's
examples/mixed_precision.cpp (float preconditioner, double solver)."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np
import jax.numpy as jnp

from amgcl_tpu import make_solver, AMGParams
from amgcl_tpu.solver.cg import CG
from amgcl_tpu.utils.sample_problem import poisson3d

A, rhs = poisson3d(32)
solve = make_solver(A, AMGParams(dtype=jnp.float32), CG(tol=1e-10),
                    solver_dtype=jnp.float64)
x, info = solve(rhs)
r = np.linalg.norm(rhs - A.spmv(np.asarray(x))) / np.linalg.norm(rhs)
print("f32 precond / f64 solver: %d iterations, true residual %.2e"
      % (info.iters, r))

# The TPU-native alternative: solve ENTIRELY in f32 and recover the
# accuracy with iterative refinement whose outer residual is evaluated
# in compensated two-f32 arithmetic (ops/dfloat.py) — float64-class
# residuals without touching f64 compute, which TPUs emulate in
# software (refine_dtype='auto' picks this on TPU automatically).
solve_df = make_solver(A, AMGParams(dtype=jnp.float32),
                       CG(tol=1e-7), refine=3, refine_dtype="df32")
x2, info2 = solve_df(rhs)
r2 = np.linalg.norm(rhs - A.spmv(np.asarray(x2, np.float64))) \
    / np.linalg.norm(rhs)
print("f32 + df32-refinement:    %d iterations, true residual %.2e"
      % (info2.iters, r2))
