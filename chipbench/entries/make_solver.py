"""The entry users call: ``amgcl_tpu.make_solver``, as the configuration
states it (``precond``, ``solver``, ``refine``).

An entry module gives the harness: ``build(config, A)``, the callable
the window drives, ``solver(b) -> (x, info)``; ``control(config, A,
solver)``, the same with the configuration's ``control`` applied;
``tolerance(config)``, the limit of the comparison; ``report(info,
tol)``, a solve's (iterations, whether the program reports it sound);
``summary(solver)``, a JSON-able line on what set-up built; and
``describe(solver)``, the counts ``chipbench/work.py`` needs, or None.
"""

from __future__ import annotations

from chipbench import work


def build(config, A, precond=None, refine=None):
    """``precond`` reuses a built hierarchy, ``refine`` overrides the
    refinement passes."""
    from amgcl_tpu import make_solver
    from amgcl_tpu.models.runtime import (precond_params_from_dict,
                                          solver_from_params)
    if precond is None:
        precond = precond_params_from_dict(config["precond"])
    return make_solver(A, precond, solver_from_params(config["solver"]),
                       refine=config["refine"] if refine is None else refine)


def control(config, A, solver):
    """The same hierarchy and solver with the configuration's control
    (refinement off: float32 throughout)."""
    return build(config, A, precond=solver.precond,
                 refine=int(config["control"]["refine"]))


def tolerance(config) -> float:
    return float(config["solver"]["tol"])


def report(info, tol: float):
    health = getattr(info, "health", None) or {}
    ok = bool(info.resid <= tol) and health.get("ok", True)
    return int(info.iters), ok


def summary(solver):
    return {"refine_mode": solver.refine_mode,
            "formats": [type(lv.A).__name__
                        for lv in solver.precond.hierarchy.levels]}


def describe(solver):
    return work.describe(solver)
