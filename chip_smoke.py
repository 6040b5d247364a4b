#!/usr/bin/env python3
"""Chip smoke test: amgcl_tpu's main path, once, on a TPU.

    python chip_smoke.py              # one chip: headline + unstructured
    python chip_smoke.py --chips 4    # four chips: the distributed phase

One chip drives two phases through ``make_solver``, the entry point users
call:

* headline: 3D Poisson 128^3 (2,097,152 rows, ~14.6M nnz), smoothed
  aggregation + CG + SPAI-0 on a float32 hierarchy, ``refine=3``; one
  setup, three solves;
* unstructured: the seeded FE-like operator (85,623 rows, ~2.63M nnz),
  smoothed aggregation on a float32 hierarchy + BiCGStab, ``refine=2``;
  ``make_solver`` itself decides the reorder and the per-level formats.

``--chips 4`` runs only the distributed phase: a ``DistAMGSolver`` over a
four-device mesh on the same Poisson system, set up as ``__graft_entry__``
sets it up, and a one-chip ``make_solver`` solve of it on device 0 with the
same hierarchy parameters to compare with. Both run float32 solves inside a
host float64 refinement loop, so that their solutions agree far below the
tolerance.

Every solve's true residual ``||b - A x|| / ||b||`` is computed on the host
in float64 with scipy and must be at most 1e-6. A Pallas kernel that
declines its probe compile or on-device value check
(``ops.pallas_spmv.PROBE_DECLINES``) fails the run, and so does a df32
refinement that fell back to float64.

With no TPU the script exits nonzero and prints no result. Otherwise the
earlier lines of standard output are one JSON object per measurement, and
the last line is the result, ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

TOL = 1e-6
#: the four-chip phase refines both solutions this far below TOL, so that
#: their difference measures the distributed execution, not the stopping
#: point
REFINE_TOL = 1e-11
POISSON_N = 128
FE_ROWS = 85623


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def true_resid(A_sp, b, x):
    """||b - A x|| / ||b|| on the host in float64."""
    x = np.asarray(x, np.float64)
    return float(np.linalg.norm(b - A_sp @ x) / np.linalg.norm(b))


def level_formats(solver):
    return [type(lv.A).__name__ for lv in solver.precond.hierarchy.levels]


def compile_totals():
    """Backend compiles and their seconds so far in this process."""
    from amgcl_tpu.telemetry import compile_watch
    tot = compile_watch.snapshot()["totals"]
    return {"backend_compiles": tot["backend_compiles"],
            "compile_s": tot["compile_s"]}


def setup_stages(amg, top=6):
    """The slowest top-level setup stages of an AMG, [stage, seconds]."""
    rows = [r for r in amg.setup_report().get("rows", [])
            if not r.get("nested")]
    rows.sort(key=lambda r: -r["seconds"])
    return [[r["stage"], r["seconds"]] for r in rows[:top]]


def lowering(info):
    return (info.compile or {}).get("lowering") \
        or info.extra.get("lowering")


def timed_solve(jax, solver, b):
    t0 = time.perf_counter()
    x, info = solver(b)
    jax.block_until_ready(x)
    return x, info, time.perf_counter() - t0


def headline(jax, jnp, n=POISSON_N):
    """Poisson n^3 through make_solver: one setup, three solves."""
    from amgcl_tpu import AMGParams, make_solver
    from amgcl_tpu.solver.cg import CG
    from amgcl_tpu.utils.sample_problem import poisson3d

    A, rhs = poisson3d(n)
    A_sp = A.to_scipy()
    t0 = time.perf_counter()
    s = make_solver(A, AMGParams(dtype=jnp.float32),
                    CG(maxiter=100, tol=1e-6), refine=3)
    setup_s = time.perf_counter() - t0
    levels = s.precond.hierarchy.levels
    fused = " ".join(
        "%d%s%s" % (i, "d" if lv.down is not None else "",
                    "u" if lv.up is not None else "")
        for i, lv in enumerate(levels)
        if lv.down is not None or lv.up is not None)
    emit("headline", rows=A.nrows, nnz=A.nnz, setup_s=setup_s,
         setup_stages=setup_stages(s.precond), compile=compile_totals(),
         formats=level_formats(s), fused_levels=fused,
         refine_mode=s.refine_mode)
    check(not s.refine_fallback, "headline: the df32 self-check fell back "
          "to float64 refinement")
    b = jnp.asarray(rhs, jnp.float32)
    for k in range(3):
        x, info, wall = timed_solve(jax, s, b)
        tr = true_resid(A_sp, rhs, x)
        emit("headline", solve=k, wall_s=wall, iters=int(info.iters),
             resid=float(info.resid), true_resid=tr,
             lowering=lowering(info), compile=compile_totals())
        check(tr <= TOL, "headline solve %d: true residual %.3g > %g"
              % (k, tr, TOL))


def unstructured(jax, jnp, rows=FE_ROWS):
    """The seeded FE-like operator through make_solver; the reorder and
    the formats are make_solver's own decisions."""
    from amgcl_tpu import AMGParams, make_solver
    from amgcl_tpu.ops.unstructured import fe_like_problem
    from amgcl_tpu.solver.bicgstab import BiCGStab

    A, rhs = fe_like_problem(n=rows)
    A_sp = A.to_scipy()
    t0 = time.perf_counter()
    s = make_solver(A, AMGParams(dtype=jnp.float32),
                    BiCGStab(maxiter=300, tol=1e-8), refine=2)
    setup_s = time.perf_counter() - t0
    plan = s._reorder
    decisions = [[d["fmt"], d["reason"]] if d else None
                 for d in (s.precond._format_decisions or [])]
    emit("unstructured", rows=A.nrows, nnz=A.nnz, setup_s=setup_s,
         setup_stages=setup_stages(s.precond), compile=compile_totals(),
         formats=level_formats(s), decisions=decisions,
         reorder=plan["variant"] if plan is not None else None,
         refine_mode=s.refine_mode)
    check(not s.refine_fallback, "unstructured: the df32 self-check fell "
          "back to float64 refinement")
    x, info, wall = timed_solve(jax, s, jnp.asarray(rhs, jnp.float32))
    tr = true_resid(A_sp, rhs, x)
    emit("unstructured", wall_s=wall, iters=int(info.iters),
         resid=float(info.resid), true_resid=tr, lowering=lowering(info),
         compile=compile_totals())
    check(tr <= TOL, "unstructured: true residual %.3g > %g" % (tr, TOL))


def refined(solve, A_sp, rhs, tol=REFINE_TOL, passes=5):
    """Mixed-precision iterative refinement on the host: each pass solves
    for the scaled float64 residual in float32 and adds the correction in
    float64, until the true residual is at most ``tol``. ``solve(r32)``
    returns ``(x, info)``. Returns x and one row per pass."""
    nb = np.linalg.norm(rhs)
    x = np.zeros_like(rhs)
    r = rhs
    rows = []
    for _ in range(passes):
        scale = np.linalg.norm(r)
        t0 = time.perf_counter()
        dx, info = solve((r / scale).astype(np.float32))
        dx = np.asarray(dx, np.float64)
        wall = time.perf_counter() - t0
        x = x + scale * dx
        r = rhs - A_sp @ x
        rows.append({"wall_s": wall, "iters": int(info.iters),
                     "resid": float(info.resid),
                     "true_resid": float(np.linalg.norm(r) / nb)})
        if rows[-1]["true_resid"] <= tol:
            break
    return x, rows


def distributed(jax, jnp, n=POISSON_N, n_devices=4):
    """DistAMGSolver over an n_devices mesh against a one-chip make_solver
    solve of the same Poisson system on device 0, with the same hierarchy
    parameters: smoothed aggregation without the stencil setup (the
    distributed solver shards explicit transfer operators), ILU0, coarse
    levels below 500 rows, float32."""
    from amgcl_tpu import AMGParams, make_solver
    from amgcl_tpu.coarsening.smoothed_aggregation import \
        SmoothedAggregation
    from amgcl_tpu.parallel.dist_amg import DistAMGSolver
    from amgcl_tpu.parallel.mesh import make_mesh
    from amgcl_tpu.relaxation.ilu0 import ILU0
    from amgcl_tpu.solver.cg import CG
    from amgcl_tpu.utils.sample_problem import poisson3d

    devices = jax.devices()
    check(len(devices) >= n_devices, "%d devices, need %d"
          % (len(devices), n_devices))
    A, rhs = poisson3d(n)
    A_sp = A.to_scipy()
    t0 = time.perf_counter()
    d = DistAMGSolver(A, make_mesh(n_devices),
                      AMGParams(dtype=jnp.float32, coarse_enough=500,
                                relax=ILU0()),
                      CG(maxiter=100, tol=TOL), device_mis=True,
                      replicate_below=600)
    emit("distributed", devices=n_devices, setup_s=time.perf_counter() - t0,
         setup_stages=setup_stages(d.host_amg), compile=compile_totals(),
         sharded_levels=len(d.hier.levels),
         replicated_levels=len(d.hier.rep.levels))
    x_dist, passes = refined(d, A_sp, rhs)
    held = [int((dv.memory_stats() or {}).get("bytes_in_use", 0))
            for dv in devices[:n_devices]]
    tr = passes[-1]["true_resid"]
    emit("distributed", passes=passes, true_resid=tr, bytes_in_use=held,
         compile=compile_totals())
    check(tr <= TOL, "distributed: true residual %.3g > %g" % (tr, TOL))
    total = sum(held)
    check(total > 0 and min(held) >= 0.1 * total / n_devices,
          "distributed: a device holds (almost) nothing: %s" % held)
    del d

    t0 = time.perf_counter()
    s = make_solver(A, AMGParams(
        dtype=jnp.float32, coarse_enough=500, relax=ILU0(),
        coarsening=SmoothedAggregation(stencil_setup=False)),
        CG(maxiter=100, tol=TOL))
    emit("one_chip", setup_s=time.perf_counter() - t0,
         setup_stages=setup_stages(s.precond), compile=compile_totals(),
         formats=level_formats(s))
    x_one, passes = refined(s, A_sp, rhs)
    tr1 = passes[-1]["true_resid"]
    diff = float(np.linalg.norm(x_dist - x_one) / np.linalg.norm(x_one))
    emit("one_chip", passes=passes, true_resid=tr1,
         rel_diff_vs_distributed=diff, compile=compile_totals())
    check(tr1 <= TOL, "one-chip: true residual %.3g > %g" % (tr1, TOL))
    check(diff <= TOL, "distributed and one-chip solutions differ by "
          "%.3g > %g" % (diff, TOL))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the distributed phase on four chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu":
        print("chip_smoke: no TPU (JAX platform %r)" % dev0.platform,
              file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # JAX reads the variable itself when it is set; else a fixed path
        # (part of the cache key), so that later runs hit
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from amgcl_tpu import native
    from amgcl_tpu.ops.pallas_spmv import PROBE_DECLINES

    emit("device", platform=dev0.platform, kind=dev0.device_kind,
         count=len(devices), native_setup=native.lib() is not None)
    try:
        if args.chips == 4:
            distributed(jax, jnp)
        else:
            headline(jax, jnp)
            unstructured(jax, jnp)
        for name, reason in PROBE_DECLINES:
            emit("kernel_decline", kernel=name, reason=reason)
        check(not PROBE_DECLINES, "%d Pallas kernel(s) declined"
              % len(PROBE_DECLINES))
    except SmokeFailure as e:
        print("chip_smoke: FAIL: %s" % e, file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
