"""Solve a generated 3D Poisson problem — the minimal end-to-end example
(the reference's examples/solver.cpp with a generated problem).

    python examples/poisson.py [n]
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

from amgcl_tpu import make_solver, AMGParams
from amgcl_tpu.solver.cg import CG
from amgcl_tpu.utils.sample_problem import poisson3d
from amgcl_tpu.utils.profiler import Profiler


def main(n=48):
    prof = Profiler()
    with prof.scope("generate"):
        A, rhs = poisson3d(n)
    with prof.scope("setup"):
        solve = make_solver(A, AMGParams(), CG(tol=1e-6), refine=2)
    with prof.scope("solve"):
        x, info = solve(rhs)
    print(solve)
    print("Iterations: %d\nError:      %.3e" % (info.iters, info.resid))
    print()
    print(prof)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 48)
