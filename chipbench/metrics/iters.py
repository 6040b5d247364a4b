"""iters. Layer: solver (``solver/cg.py``, ``solver/bicgstab.py``,
refinement in ``models/make_solver.py``). Moves: solve_ms.

Krylov iterations per solve, every refinement pass included, as the
``SolveReport`` each call returns counts them: the mean over the traced
solves."""


def read(rec):
    its = rec["iters"]
    return sum(its) / len(its) if its else None
