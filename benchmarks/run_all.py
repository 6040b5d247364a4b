"""Local benchmark suite across the problem classes the reference tracks
(BASELINE.md): scalar Poisson, block system, saddle point (Schur),
non-symmetric convection, and the distributed mesh path. Prints a table and
writes benchmarks/RESULTS_<device>.md.

The driver-facing headline benchmark stays in /bench.py (one JSON line);
this suite is for humans comparing configurations.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import numpy as np


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


def main():
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import scipy.sparse as sp

    from amgcl_tpu import make_solver, AMGParams, CSR
    from amgcl_tpu.solver.cg import CG
    from amgcl_tpu.solver.bicgstab import BiCGStab
    from amgcl_tpu.solver.gmres import FGMRES
    from amgcl_tpu.models.schur import SchurPressureCorrection
    from amgcl_tpu.models.cpr import CPR
    from amgcl_tpu.relaxation.ilu0 import ILU0
    from amgcl_tpu.utils.sample_problem import (poisson3d,
                                                convection_diffusion_2d)

    rows = []

    def bench(name, build, solve_args=None):
        t_setup, solver = timed(build)
        rhs = solve_args
        x, info = solver(rhs)                       # compile + solve
        jax.block_until_ready(x)
        t_solve, (x, info) = timed(lambda: solver(rhs))
        jax.block_until_ready(x)
        rows.append((name, t_setup, t_solve, info.iters, float(info.resid)))
        print("%-38s setup %6.2fs solve %6.3fs iters %3d resid %.1e"
              % rows[-1])

    # 1. scalar 3D Poisson, SA + CG + spai0 (the headline config)
    A, rhs = poisson3d(64)
    bench("poisson3d_64 sa+cg+spai0 f32+refine",
          lambda: make_solver(A, AMGParams(dtype=jnp.float32),
                              CG(tol=1e-6), refine=3), rhs)

    # 2. block system (Serena-style value types), spai0
    b = 3
    Ap, _ = poisson3d(16)
    K = sp.kron(Ap.to_scipy(), np.eye(b)).tocsr()
    Ab = CSR.from_scipy(K).to_block(b)
    rb = np.ones(Ab.nrows * b)
    bench("block3x3 sa+cg+spai0 f64",
          lambda: make_solver(Ab, AMGParams(dtype=jnp.float64,
                                            coarse_enough=600),
                              CG(tol=1e-8)), rb)

    # 3. Stokes-type saddle point, Schur pressure correction
    n = 24
    T = sp.diags([-np.ones(n - 1), 2 * np.ones(n), -np.ones(n - 1)],
                 [-1, 0, 1])
    L = (sp.kron(sp.identity(n), T) + sp.kron(T, sp.identity(n))).tocsr()
    nu = L.shape[0]
    Avv = sp.block_diag([L, L]).tocsr()
    D = sp.diags([-np.ones(nu - 1), np.ones(nu)], [-1, 0], shape=(nu, nu))
    B = sp.hstack([D, 0.5 * D]).tocsr()
    Ks = sp.bmat([[Avv, B.T], [B, -1e-2 * sp.identity(nu)]]).tocsr()
    pmask = np.zeros(Ks.shape[0], dtype=bool)
    pmask[2 * nu:] = True
    rs = np.ones(Ks.shape[0])
    bench("stokes schur_pc + fgmres f64",
          lambda: make_solver(
              Ks, SchurPressureCorrection(
                  Ks, pmask, AMGParams(dtype=jnp.float64),
                  AMGParams(dtype=jnp.float64), dtype=jnp.float64),
              FGMRES(maxiter=300, tol=1e-8)), rs)

    # 4. non-symmetric convection-diffusion, ILU0 + BiCGStab
    Ac, rc = convection_diffusion_2d(96, eps=0.02)
    bench("convection96 ilu0+bicgstab f64",
          lambda: make_solver(Ac, AMGParams(relax=ILU0(),
                                            dtype=jnp.float64),
                              BiCGStab(maxiter=200, tol=1e-8)), rc)

    # 5. distributed AMG over the local mesh
    from amgcl_tpu.parallel.mesh import make_mesh
    from amgcl_tpu.parallel.dist_amg import DistAMGSolver
    mesh = make_mesh()
    Am, rm = poisson3d(32)
    bench("dist poisson3d_32 over %d devices" % len(jax.devices()),
          lambda: DistAMGSolver(Am, mesh, AMGParams(dtype=jnp.float64),
                                CG(tol=1e-8)), rm)

    dev = jax.devices()[0].platform
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "RESULTS_%s.md" % dev)
    with open(path, "w") as f:
        f.write("# Benchmark results (%s)\n\n" % jax.devices()[0])
        f.write("| case | setup (s) | solve (s) | iters | resid |\n")
        f.write("|---|---|---|---|---|\n")
        for r in rows:
            f.write("| %s | %.2f | %.3f | %d | %.1e |\n" % r)
    print("\nwrote", path)


if __name__ == "__main__":
    main()
