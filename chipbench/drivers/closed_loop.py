"""Closed loop, one caller: solve after solve until the window's seconds
have passed; the solve in flight then finishes and counts.

Traffic keys: ``callers`` (1), the right-hand sides (``rhs``,
``case_seed``, ``ordered_cases``: ``chipbench/rhs.py``) and
``check_sample``, the number of the window's solutions, drawn from the
seed, that go to the reference. Each solve is timed from the call to the
end of ``block_until_ready``; the next case is made on the device right
after, while the host files the report. With a trace, the window's first
``ctx.trace_seconds`` run under the profiler and the rest untraced, so
every run solves for ``ctx.seconds``.

Record fields: ``window_s``, ``solve_s`` (every solve), ``iters`` (the
traced solves, or every solve without a trace), ``attempted``,
``failed``, ``trace`` and ``sample``, the (right-hand side, solution)
pairs to check.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import devtrace, rhs
from chipbench.spec import SpecError


def prepare(ctx):
    """The run's case stream."""
    if int(ctx.traffic.get("callers", 1)) != 1:
        raise SpecError("a closed loop drives one caller; traffic "
                            "asks for %r" % ctx.traffic.get("callers"))
    return rhs.Cases(ctx.jax, ctx.traffic, ctx.seed, ctx.rows)


def warm(ctx, cases):
    """One solve of a vector no case uses: compiles, or loads from the
    cache, the solve program and the case generator."""
    x, _ = ctx.solver(cases.warm())
    x.block_until_ready()


def window(ctx, cases):
    annotate = ctx.jax.profiler.TraceAnnotation
    rng = np.random.default_rng(rhs.seed_words(ctx.seed, 1))
    keep = int(ctx.traffic["check_sample"])
    out = {"solve_s": [], "iters": [], "failed": 0}
    sample = []
    state = {"b": cases.make(cases.case(0))}
    t0 = time.perf_counter()

    def solve_until(deadline):
        with annotate("chipbench/window"):
            while True:
                i = len(out["solve_s"])
                s0 = time.perf_counter()
                with annotate("chipbench/solve_call"):
                    x, info = ctx.solver(state["b"])
                with annotate("chipbench/block_until_ready"):
                    x.block_until_ready()
                s1 = time.perf_counter()
                with annotate("chipbench/rhs"):
                    state["b"] = cases.make(cases.case(i + 1))
                with annotate("chipbench/report"):
                    its, ok = ctx.entry.report(info, ctx.tol)
                    out["solve_s"].append(s1 - s0)
                    out["iters"].append(its)
                    out["failed"] += not ok
                    if i < keep:
                        sample.append((cases.case(i), x))
                    else:
                        j = int(rng.integers(0, i + 1))
                        if j < keep:
                            sample[j] = (cases.case(i), x)
                if s1 - t0 >= deadline:
                    return s1

    if ctx.trace_seconds is None:
        t1 = solve_until(ctx.seconds)
    else:
        t1, out["trace"] = devtrace.capture(
            ctx.jax, lambda: solve_until(min(ctx.trace_seconds, ctx.seconds)))
        traced = len(out["solve_s"])
        if t1 - t0 < ctx.seconds:
            t1 = solve_until(ctx.seconds)
        del out["iters"][traced:]
    out["window_s"] = t1 - t0
    out["attempted"] = len(out["solve_s"])
    out["sample"] = [(cases.make(c), x) for c, x in sample]
    return out
