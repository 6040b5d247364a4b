"""The TPU kernel-fusion tiers and their observability/control knobs.

On a real TPU the DIA paths run hand-written Pallas kernels (tier 1:
single-pass spmv / residual / smoother sweeps / spmv+dots; tier 2: whole
V-cycle legs at stencil levels). This example runs on CPU by forcing the
kernels through interpret mode (the CI hook) purely to DEMONSTRATE the
wiring — on CPU the interpret kernels are slower than XLA; on TPU the
real kernels are the fast path and engage automatically.

Knobs:
  AMGCL_TPU_PALLAS=0            kill ALL Pallas paths (XLA lowering)
  AMGCL_TPU_FUSED_VCYCLE=0      kill only the whole-leg sweep kernels
  AMGCL_TPU_PALLAS_INTERPRET=1  force interpret mode off-TPU (CI/demo)
"""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))

os.environ.setdefault("AMGCL_TPU_PALLAS_INTERPRET", "1")

import numpy as np
import scipy.sparse as sp
import jax.numpy as jnp

from amgcl_tpu import make_solver, AMGParams
from amgcl_tpu.solver.cg import CG


def grid_laplacian(d2, d1, d0):
    def T(n):
        e = np.ones(n)
        return sp.diags([-e[:-1], 2 * e, -e[:-1]], [-1, 0, 1],
                        format="csr")
    I = sp.identity
    A = (sp.kron(I(d2), sp.kron(I(d1), T(d0)))
         + sp.kron(I(d2), sp.kron(T(d1), I(d0)))
         + sp.kron(T(d2), sp.kron(I(d1), I(d0)))).tocsr()
    A.sort_indices()
    return A


def main():
    # lane-packable grid: f0 | 128 keeps the MXU pair reductions legal
    A = grid_laplacian(8, 16, 128)
    rhs = np.ones(A.shape[0])

    solve = make_solver(A, AMGParams(dtype=jnp.float32, coarse_enough=300),
                        CG(tol=1e-6, maxiter=60))
    x, info = solve(rhs)
    print(solve)           # the repr lists fused V-cycle kernel coverage
    print("iters %d  resid %.2e" % (info.iters, info.resid))

    lv0 = solve.precond.hierarchy.levels[0]
    print("level-0 handles: down=%s (zero-guess=%s)  up=%s (hp=%s)"
          % (lv0.down is not None,
             lv0.down is not None and lv0.down.w is not None,
             lv0.up is not None,
             getattr(lv0.up, "halo_planes", None)))

    # -- an irregular FE-class matrix after RCM: to_device('auto') picks
    # the dense-window kernel on a TPU and windowed ELL (XLA gather)
    # elsewhere
    from amgcl_tpu.ops.unstructured import fe_like_problem
    from amgcl_tpu.ops import device as dev
    from amgcl_tpu.utils.adapters import cuthill_mckee, permute
    # small on purpose: under the interpret hook (this example's default
    # off-TPU) every kernel step is emulated, so the demo problem stays
    # tiny; on a real TPU scale n up freely
    Af, rf = fe_like_problem(n=1500, nnz_target=1500 * 12, seed=1)
    p = cuthill_mckee(Af)
    Ap, rp = permute(Af, p), rf[p]
    M = dev.to_device(Ap, "auto", jnp.float32)
    print("unstructured device format: %s (win=%s)"
          % (type(M).__name__, getattr(M, "win", "-")))
    sf = make_solver(Ap, AMGParams(), CG(tol=1e-4, maxiter=60))
    xf, inf_f = sf(rp)
    print("FE-class solve: iters %d  resid %.2e" % (inf_f.iters,
                                                    inf_f.resid))


if __name__ == "__main__":
    main()
