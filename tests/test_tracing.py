"""The span recorder (telemetry/tracing.py): nesting and solve ids, the
bound and the per-name totals, appends from several threads, the Chrome
export, the solve spans of ``make_solver`` and the set-up stage spans of
both set-up paths."""

import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from amgcl_tpu.models.amg import AMG, AMGParams
from amgcl_tpu.models.make_solver import make_solver
from amgcl_tpu.solver import CG, BiCGStab
from amgcl_tpu.telemetry import tracing
from amgcl_tpu.telemetry.tracing import (RECORDER, RequestSpans,
                                         SpanRecorder, solve_span, span)
from amgcl_tpu.utils.profiler import Profiler
from amgcl_tpu.utils.sample_problem import poisson3d


@pytest.fixture
def rec():
    """The process-global recorder, emptied for the test."""
    RECORDER.clear()
    yield RECORDER
    RECORDER.clear()


def by_name(spans, name):
    return [s for s in spans if s[0] == name]


def inside(child, parent):
    return parent[1] <= child[1] <= child[2] <= parent[2]


def test_nesting_parent_and_shared_solve_id(rec):
    with span("outer", tag=1):
        with solve_span(first_call=True):
            with span("solve/prepare"):
                pass
            with span("solve/report"):
                with span("solve/report/health"):
                    pass
        with solve_span(first_call=False):
            with span("solve/prepare"):
                pass
    got = rec.spans()
    # appended as they close: children before their parents
    assert [s[0] for s in got] == [
        "solve/prepare", "solve/report/health", "solve/report", "solve",
        "solve/prepare", "solve", "outer"]
    outer = by_name(got, "outer")[0]
    assert outer[3] is None and outer[4] is None and outer[5] == {"tag": 1}
    s1, s2 = by_name(got, "solve")
    assert s1[3] == s2[3] == "outer"
    assert s1[4] is not None and s2[4] == s1[4] + 1
    assert s1[5] == {"first_call": True}
    health = by_name(got, "solve/report/health")[0]
    assert health[3] == "solve/report" and health[4] == s1[4]
    p1, p2 = by_name(got, "solve/prepare")
    assert (p1[4], p2[4]) == (s1[4], s2[4])
    assert all(inside(s, outer) for s in got)


def test_span_recorded_when_the_body_raises(rec):
    with pytest.raises(ValueError):
        with span("boom"):
            raise ValueError("x")
    with span("after"):
        pass
    got = rec.spans()
    assert [s[0] for s in got] == ["boom", "after"]
    assert got[1][3] is None          # the stack unwound


def test_bound_keeps_newest_and_totals_survive_eviction():
    r = SpanRecorder(max_spans=10)
    for i in range(25):
        r.record("a" if i % 2 else "b", float(i), i + 0.5)
    ring = r.spans()
    assert len(ring) == 10 and r.evicted == 15
    assert [s[1] for s in ring] == [float(i) for i in range(15, 25)]
    tot = r.totals()
    assert tot["a"]["count"] == 12 and tot["b"]["count"] == 13
    assert tot["a"]["total_s"] == pytest.approx(6.0)
    assert tot["b"]["first_s"] == pytest.approx(0.5)
    r.record_many([("c", 0.0, 1.0, None, None, None)] * 30)
    assert len(r.spans()) == 10 and r.evicted == 45
    assert r.totals()["c"]["count"] == 30
    assert r.spans("c") == r.spans()


@pytest.mark.parametrize("max_spans", [65536, 64], ids=["ring", "evicting"])
def test_appends_from_several_threads(monkeypatch, max_spans):
    """More threads than cores, switching often: no span is lost from
    the ring or the totals, also while spans are evicted."""
    import os
    import sys
    rec = SpanRecorder(max_spans=max_spans)
    monkeypatch.setattr(tracing, "RECORDER", rec)
    n_threads, n_spans = 2 * (os.cpu_count() or 4), 300
    start = threading.Barrier(n_threads)

    def work(k):
        start.wait()
        for _ in range(n_spans):
            with span("t%d" % k):
                with span("t%d/inner" % k):
                    pass

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    total = 2 * n_threads * n_spans
    got = rec.spans()
    assert len(got) == min(total, max_spans)
    assert rec.evicted == total - len(got)
    tot = rec.totals()
    for k in range(n_threads):
        assert tot["t%d" % k]["count"] == n_spans
        assert tot["t%d/inner" % k]["count"] == n_spans
        # each thread's stack is its own: the parent is never another
        # thread's span
        assert {s[3] for s in by_name(got, "t%d/inner" % k)} <= {"t%d" % k}


def test_chrome_export_and_serve_track_on_shared_epoch(rec):
    prof = Profiler()
    epoch = prof._t0
    spans = RequestSpans(max_events=4)
    with prof.scope("cli"):
        t = time.perf_counter()
        spans.add(7, [("queue", t, t + 0.001)])
        spans.add(1, [("pad", t, t + 0.002), ("solve", t + 0.002, t + 0.01),
                      ("sync", t + 0.01, t + 0.011)], label="batch")
        with span("solve/fetch"):
            pass
        time.sleep(0.012)
    assert [p for p, _, _ in spans.events] == [
        "req00007/queue", "batch00001/pad", "batch00001/solve",
        "batch00001/sync"]
    assert spans.events[2] == ("batch00001/solve", t + 0.002, t + 0.01)
    trace = spans.to_chrome_trace(tid=3, tid_name="serve requests",
                                  epoch=epoch)
    evs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in evs] == ["queue", "pad", "solve", "sync"]
    assert all(e["cat"] == "amgcl/serve" and e["tid"] == 3 for e in evs)
    assert not [e for e in trace["traceEvents"]
                if e["name"] == "spans_dropped"]
    solve_ev = evs[2]
    assert solve_ev["args"]["path"] == "batch00001/solve"
    assert solve_ev["ts"] == pytest.approx((t + 0.002 - epoch) * 1e6,
                                           abs=1e-2)
    # the CLI track shares the epoch: the serve spans land inside it
    cli = [e for e in prof.to_chrome_trace(epoch=epoch)["traceEvents"]
           if e.get("name") == "cli"][0]
    assert cli["ts"] <= evs[0]["ts"]
    assert solve_ev["ts"] + solve_ev["dur"] <= cli["ts"] + cli["dur"]
    # a fifth span through a bound of four: the oldest goes, and the
    # export says so
    spans.add(8, [("queue", t + 0.02, t + 0.03)])
    assert spans.dropped == 1 and len(spans.events) == 4
    assert spans.events[0][0] == "batch00001/pad"
    drop = [e for e in spans.to_chrome_trace(epoch=epoch)["traceEvents"]
            if e["name"] == "spans_dropped"]
    assert drop and drop[0]["args"] == {"dropped": 1, "cap": 4}
    # the process recorder has the serve phases too, by name, and its
    # own export carries the solve-path span
    tot = rec.totals()
    assert tot["serve/queue"]["count"] == 2
    assert tot["serve/solve"]["count"] == 1
    g = rec.to_chrome_trace(epoch=epoch)["traceEvents"]
    fetch = [e for e in g if e["name"] == "fetch"][0]
    assert fetch["cat"] == "amgcl" and \
        fetch["args"]["path"] == "solve/fetch"


def test_span_on_the_profiler_timeline(tmp_path):
    """While a jax.profiler trace is taken, a span is also a host event
    ``amgcl/<name>`` of that trace."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("probe_span"):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events}
    assert "amgcl/probe_span" in names


SOLVE_CHILDREN = ("solve/prepare", "solve/dispatch", "solve/fetch",
                  "solve/report")


def test_one_solve_span_per_call_with_four_children(rec):
    A, rhs = poisson3d(8)
    s = make_solver(A, AMGParams(dtype=jnp.float64, coarse_enough=100),
                    CG(maxiter=100, tol=1e-8))
    RECORDER.clear()
    for _ in range(3):
        s(rhs)
    got = rec.spans()
    solves = by_name(got, "solve")
    assert len(solves) == 3
    assert [sp[5]["first_call"] for sp in solves] == [True, False, False]
    assert all(sp[5]["batched"] is False for sp in solves)
    assert len({sp[4] for sp in solves}) == 3
    for sp in solves:
        kids = [c for c in got if c[4] == sp[4] and c[3] == "solve"]
        assert [c[0] for c in kids] == list(SOLVE_CHILDREN)
        assert all(inside(c, sp) for c in kids)
        # the children follow one another
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        report = kids[-1]
        legs = [c for c in got if c[3] == "solve/report" and c[4] == sp[4]]
        assert {c[0] for c in legs} >= {"solve/report/health",
                                        "solve/report/compile_watch"}
        assert all(inside(c, report) for c in legs)


def test_recovery_ladder_attempts_are_solve_spans(rec):
    from amgcl_tpu.faults.recovery import RecoveryPolicy
    A, rhs = poisson3d(8)
    s = make_solver(A, AMGParams(dtype=jnp.float64, coarse_enough=100),
                    CG(maxiter=100, tol=1e-8),
                    recovery=RecoveryPolicy())
    RECORDER.clear()
    s(rhs)
    solves = by_name(rec.spans(), "solve")
    assert len(solves) >= 1
    assert all(sp[3] is None for sp in solves)


@pytest.fixture
def device_setup(monkeypatch):
    monkeypatch.setenv("AMGCL_TPU_DEVICE_SETUP", "1")


def _stencil_problem():
    A, rhs = poisson3d(16)
    return A, rhs, AMGParams(dtype=jnp.float32, coarse_enough=200), CG(
        maxiter=100, tol=1e-6)


def _fe_problem():
    from amgcl_tpu.ops.unstructured import fe_like_problem
    A, rhs = fe_like_problem(2000, 40000, seed=0)
    return A, rhs, AMGParams(dtype=jnp.float32, coarse_enough=200), \
        BiCGStab(maxiter=300, tol=1e-6)


#: stage names both set-up paths report under ``setup/hierarchy``
SHARED_STAGES = ("setup/level0/transfer", "setup/level0/galerkin",
                 "setup/level0/fused_kernels", "setup/coarse_solver")


@pytest.mark.parametrize("path", ["device", "hybrid", "host"])
def test_setup_stage_names_on_both_paths(rec, monkeypatch, path):
    if path == "host":
        A, rhs, prm, solver = _fe_problem()
    else:
        monkeypatch.setenv("AMGCL_TPU_DEVICE_SETUP", "1")
        A, rhs, prm, solver = _stencil_problem()
        if path == "hybrid":
            # level 2's stencil outgrows the device path: the host loop
            # builds the rest
            A, rhs = poisson3d(24)
            prm = AMGParams(dtype=jnp.float32, coarse_enough=50)
    make_solver(A, prm, solver)
    got = rec.spans()
    [ms] = by_name(got, "setup/make_solver")
    [hier] = by_name(got, "setup/hierarchy")
    assert hier[3] == "setup/make_solver" and inside(hier, ms)
    assert hier[5]["path"] == path
    stages = [s for s in got if s[3] == "setup/hierarchy"]
    names = {s[0] for s in stages}
    assert set(SHARED_STAGES) <= names, names
    assert all(inside(s, hier) for s in stages)
    assert sum(s[2] - s[1] for s in stages) <= hier[2] - hier[1]
    # the device path has no coarsening stage of its own
    assert ("setup/level0/coarsening" in names) == (path == "host")
    if path == "hybrid":
        assert any(n.endswith("/coarsening") for n in names)
    # every set-up span carries what JAX traced and compiled inside it
    for s in [ms, hier] + stages:
        assert {"traces", "trace_s", "compiles", "compile_s"} <= set(s[5])
        assert s[5]["traces"] >= 0 and s[5]["compile_s"] >= 0
    assert hier[5]["traces"] >= sum(s[5]["traces"] for s in stages)
    assert ms[5]["traces"] >= hier[5]["traces"]


def test_device_path_stages_cover_the_hierarchy(rec, device_setup):
    A, _, prm, _ = _stencil_problem()
    amg = AMG(A, prm)
    assert amg._device_built
    got = rec.spans()
    [hier] = by_name(got, "setup/hierarchy")
    stages = [s for s in got if s[3] == "setup/hierarchy"]
    covered = sum(s[2] - s[1] for s in stages) / (hier[2] - hier[1])
    assert 0.5 < covered <= 1.0, covered
    # the build's profiler tree keeps the same stage names
    scopes = amg.setup_profile.to_dict()["scopes"]
    assert {"level0/galerkin", "level0/transfer", "coarse_solver"} \
        <= set(scopes)
    assert amg.setup_report()["coverage"] > 0.5


def test_setup_substage_span_nests_under_its_stage(rec):
    from amgcl_tpu.telemetry.tracing import setup_scope, setup_substage
    prof = Profiler()
    with setup_scope(prof, "level0/galerkin"):
        with setup_substage("galerkin_plan"):
            pass
    with setup_substage("loose"):
        pass
    got = rec.spans()
    assert [(s[0], s[3]) for s in got] == [
        ("setup/level0/galerkin/galerkin_plan", "setup/level0/galerkin"),
        ("setup/level0/galerkin", None), ("setup/loose", None)]
    assert "galerkin_plan" in \
        prof.to_dict()["scopes"]["level0/galerkin"]["children"]


def test_solve_spans_cost_within_budget(rec):
    """The nine spans of a warm solve, with no profile active, stay far
    below the per-solve work they time (the 10 us budget is checked on
    the chip host; a shared CI core gets 5x room)."""
    n, best = 2000, float("inf")
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(n):
            with solve_span(first_call=False, batched=False) as sp:
                for name in SOLVE_CHILDREN:
                    sp.step(name)
                for leg in ("health", "compile_watch", "memwatch",
                            "flight"):
                    sp.begin("solve/report/" + leg)
                    sp.end()
        best = min(best, (time.perf_counter() - t) / n * 1e6)
    assert best < 50.0, best
    tot = rec.totals()
    assert tot["solve"]["count"] == tot["solve/report/flight"]["count"] \
        == 3 * n


def test_steps_and_legs_carry_parent_and_solve_id(rec):
    with span("outer"):
        with solve_span() as sp:
            sp.begin("solve/early")
            sp.end()
            sp.step("solve/prepare")
            sp.step("solve/report")
            sp.begin("solve/report/sink")
            sp.begin("solve/report/sink/emit")
            sp.end()
            # a span inside a leg names the solve span as its parent
            with span("inner"):
                pass
            sp.end()
    got = {s[0]: s for s in rec.spans()}
    sid = got["solve"][4]
    # a leg before the first step is the solve span's own child
    assert got["solve/early"][3:5] == ("solve", sid)
    assert got["solve/prepare"][3:5] == ("solve", sid)
    assert got["solve/report"][3:5] == ("solve", sid)
    # a step ends where the next begins, the last where the span ends
    assert got["solve/prepare"][2] == got["solve/report"][1]
    assert got["solve/report"][2] == got["solve"][2]
    assert got["solve/report/sink"][3:5] == ("solve/report", sid)
    assert got["solve/report/sink/emit"][3:5] == ("solve/report/sink", sid)
    assert got["inner"][3:5] == ("solve", sid)
    assert got["solve"][3] == "outer" and got["outer"][4] is None
    assert inside(got["solve/report/sink"], got["solve/report"])
    assert inside(got["solve/report/sink/emit"], got["solve/report/sink"])


def test_legs_left_open_by_an_exception_end_with_the_span(rec):
    with pytest.raises(RuntimeError):
        with solve_span() as sp:
            sp.step("solve/report")
            sp.begin("solve/report/memwatch")
            raise RuntimeError("x")
    got = {s[0]: s for s in rec.spans()}
    assert got["solve/report/memwatch"][3] == "solve/report"
    assert got["solve/report/memwatch"][2] == got["solve"][2]
    with span("after"):
        pass
    assert rec.spans()[-1][3:5] == (None, None)   # the state unwound


def test_steps_on_the_profiler_timeline(tmp_path):
    """While a jax.profiler trace is taken, the solve span's steps and
    legs are host events of that trace too."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with solve_span() as sp:
            sp.step("solve/prepare")
            sp.step("solve/report")
            sp.begin("solve/report/health")
            sp.end()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events}
    assert {"amgcl/solve", "amgcl/solve/prepare", "amgcl/solve/report",
            "amgcl/solve/report/health"} <= names


def test_compile_watch_counts_traces_and_compiles():
    from amgcl_tpu.telemetry import compile_watch as cw
    before = cw.global_watch().counters()
    jax.jit(lambda x: x * 3 + 1)(np.arange(5.0 + 0.25)).block_until_ready()
    after = cw.global_watch().counters()
    assert after[0] >= before[0] + 1 and after[1] > before[1]
    assert after[2] >= before[2] + 1 and after[3] >= before[3]
