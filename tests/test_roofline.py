"""Roofline attribution + compile watch (ISSUE 4): measured-vs-model
stage join, device-peak detection with the CPU measured fallback, the
per-stage XLA byte cross-check, the recompile counter (repeat shape = 0
new compiles, changed shape = exactly 1), retrace findings, the Perfetto
counter track, and the profiler truncation satellite."""

import json
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from amgcl_tpu.models.amg import AMG, AMGParams
from amgcl_tpu.models.make_solver import make_solver
from amgcl_tpu.solver.cg import CG
from amgcl_tpu.telemetry import SolveReport
from amgcl_tpu.telemetry import roofline as rl
from amgcl_tpu.telemetry import compile_watch as cw
from amgcl_tpu.telemetry.health import diagnose
from amgcl_tpu.utils.profiler import Profiler
from amgcl_tpu.utils.sample_problem import poisson3d

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def amg():
    A, _ = poisson3d(12)
    return AMG(A, AMGParams(dtype=jnp.float32, coarse_enough=200))


# ---------------------------------------------------------------------------
# device peaks
# ---------------------------------------------------------------------------

def test_device_peaks_measured_fallback():
    """On CPU the peaks come from a real stream/matmul measurement, not a
    TPU table — roofline fractions in CI compare against this host."""
    pk = rl.device_peaks()
    assert pk["gbps"] and pk["gbps"] > 0
    assert pk["flops"] and pk["flops"] > 0
    if pk["platform"] == "cpu":
        assert pk["source"]["gbps"] in ("measured-stream", "env")
    json.dumps(pk)


def test_device_peaks_env_override(monkeypatch):
    monkeypatch.setenv("AMGCL_TPU_PEAK_GBPS", "123.5")
    monkeypatch.setenv("AMGCL_TPU_PEAK_FLOPS", "1e12")
    pk = rl.device_peaks(refresh=True)
    try:
        assert pk["gbps"] == 123.5 and pk["flops"] == 1e12
        assert pk["source"] == {"gbps": "env", "flops": "env"}
    finally:
        monkeypatch.delenv("AMGCL_TPU_PEAK_GBPS")
        monkeypatch.delenv("AMGCL_TPU_PEAK_FLOPS")
        rl.device_peaks(refresh=True)      # drop the override from cache


class _FakeTpu:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind,gbps,flops", [("TPU v5 lite", 819.0, 197e12),
                                             ("TPU v5e", 819.0, 197e12)])
def test_device_peaks_tpu_table(monkeypatch, kind, gbps, flops):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu(kind)])
    try:
        pk = rl.device_peaks(refresh=True)
        assert (pk["gbps"], pk["flops"]) == (gbps, flops)
        assert pk["source"] == {"gbps": "table", "flops": "table"}
    finally:
        monkeypatch.undo()
        rl.device_peaks(refresh=True)


def test_device_peaks_unknown_tpu_raises(monkeypatch):
    """A TPU missing from the table is an error, never a measured rate
    standing in for its peak."""
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeTpu("TPU v99 unknown")])
    try:
        with pytest.raises(ValueError, match="no published peaks"):
            rl.device_peaks(refresh=True)
    finally:
        monkeypatch.undo()
        rl.device_peaks(refresh=True)


# ---------------------------------------------------------------------------
# measured-vs-model join
# ---------------------------------------------------------------------------

def test_roofline_join(amg):
    rf = amg.roofline(reps=1)
    stages = rf["stages"]
    assert stages, "no stages joined"
    names = {(r["level"], r["stage"]) for r in stages}
    assert (0, "pre_smooth") in names and (0, "restrict") in names
    assert any(r["stage"] == "coarse_solve" for r in stages)
    for r in stages:
        assert r["t_s"] > 0 and r["model_bytes"] > 0
        assert r["gbps"] > 0 and r["bound"] in ("memory", "compute")
        assert r["frac_peak"] is None or r["frac_peak"] > 0
    assert rf["total"]["gbps"] > 0 and rf["cycle_s"] > 0
    # cached per build, measurement profiler rides along
    assert amg.roofline() is rf and rf["_prof"] is not None
    json.dumps({k: v for k, v in rf.items() if not k.startswith("_")})


def test_roofline_counter_track(amg):
    """The achieved-GB/s Perfetto counter track: one pair of 'C' events
    per recorded stage occurrence."""
    rf = amg.roofline()
    trace = rf["_prof"].to_chrome_trace(counters=rl.counter_map(rf))
    counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    assert counters and all(e["name"] == "achieved_gbps"
                            for e in counters)
    assert any(e["args"]["achieved_gbps"] > 0 for e in counters)


def test_xla_stage_check(amg):
    """Per-stage model bytes vs XLA cost analysis: the stage-accurate
    stages (zero-guess scaled-residual pre-smooth, dense levels, the
    dense coarse solve) agree within the ~5% ledger tolerance; gather/
    roll-paying DIA lowerings may exceed the streaming floor but are
    reported, not hidden."""
    rows = rl.xla_stage_check(amg.hierarchy)
    if not rows:
        pytest.skip("backend exposes no cost analysis")
    by = {(r["level"], r["stage"]): r for r in rows}
    assert by[(0, "pre_smooth")]["within_tol"]
    coarse = [r for r in rows if r["stage"] == "coarse_solve"]
    assert coarse and coarse[0]["within_tol"]
    assert all(r["ratio"] > 0 for r in rows)
    # the model is a floor: XLA never accesses fewer bytes than ~model
    assert all(r["ratio"] < 1.1 for r in rows)


def test_solve_roofline_classification():
    peaks = {"gbps": 10.0, "flops": 1e12}     # balance = 100 F/B
    mem = rl.solve_roofline({"flops": 10 ** 6, "bytes": 10 ** 6}, 10, 1.0,
                            peaks=peaks)
    assert mem["bound"] == "memory" and mem["frac_hbm_peak"] > 0
    comp = rl.solve_roofline({"flops": 10 ** 9, "bytes": 10 ** 3}, 10, 1.0,
                             peaks=peaks)
    assert comp["bound"] == "compute"
    assert rl.solve_roofline({"flops": 0, "bytes": 0}, 10, 1.0) is None


def test_format_roofline_renders(amg):
    rf = amg.roofline()
    txt = rl.format_roofline(rf, rl.xla_stage_check(amg.hierarchy))
    assert "Roofline" in txt and "pre_smooth" in txt
    assert "GB/s" in txt


# ---------------------------------------------------------------------------
# compile watch
# ---------------------------------------------------------------------------

def test_watched_jit_counts_and_retrace():
    @cw.watched_jit(name="t_roof.k", static_argnames=("n",))
    def k(x, n):
        return x * n

    k(jnp.ones(4), n=2)
    k(jnp.ones(4), n=2)
    k(jnp.ones(8), n=2)
    s = cw.snapshot("t_roof.k")
    assert s["calls"] == 3 and s["traces"] == 2
    assert s["cache_hits"] == 1 and s["retraces"] == 1
    assert s["signatures"] == 2
    # monitoring attribution (when the jax API exposes it)
    if s["backend_compiles"]:
        assert s["compile_s"] > 0
    fs = cw.findings(cw.snapshot())
    assert any(f["code"] == "retrace" and "t_roof.k" in f["message"]
               for f in fs)


def test_recompile_counter_same_and_changed_shape():
    """Acceptance contract: a repeated-shape solve reports ZERO new
    compiles, a changed-shape solve exactly ONE."""
    # the watch is process-global and other tests solve too — reset so
    # the retrace/warmup semantics here are deterministic
    cw.global_watch().reset()
    A, rhs = poisson3d(9)
    s = make_solver(A, AMGParams(dtype=jnp.float32, coarse_enough=150),
                    CG(maxiter=50, tol=1e-6))
    _, r1 = s(rhs)
    assert r1.compile["new_traces"] == 1
    _, r2 = s(rhs)                       # same shape: cache hit
    assert r2.compile["new_traces"] == 0
    assert r2.compile["new_backend_compiles"] == 0
    assert r2.compile["new_cache_hits"] == 1
    A2, rhs2 = poisson3d(10)             # changed shape: one new compile
    s2 = make_solver(A2, AMGParams(dtype=jnp.float32, coarse_enough=150),
                     CG(maxiter=50, tol=1e-6))
    _, r3 = s2(rhs2)
    assert r3.compile["new_traces"] == 1
    assert r3.compile["new_retraces"] == 1    # new sig after warmup
    json.dumps(r3.compile)


def test_compile_watch_disabled(monkeypatch):
    monkeypatch.setenv("AMGCL_TPU_COMPILE_WATCH", "0")
    f = cw.watched_jit(lambda x: x + 1, name="t_roof.off")
    assert not hasattr(f, "_watched_name")
    f(jnp.ones(3))
    assert cw.snapshot("t_roof.off")["calls"] == 0
    A, rhs = poisson3d(8)
    s = make_solver(A, AMGParams(dtype=jnp.float32, coarse_enough=100),
                    CG(maxiter=40, tol=1e-6))
    _, rep = s(rhs)
    assert rep.compile is None


def test_watched_jit_forwards_jit_surface():
    f = cw.watched_jit(lambda x: x * 2, name="t_roof.fw")
    f(jnp.ones(3))
    f.clear_cache()                       # the jit API tests rely on
    f(jnp.ones(3))
    assert cw.snapshot("t_roof.fw")["traces"] == 2


def test_diagnose_efficiency_findings(amg):
    rep = SolveReport(10, 1e-8, solver="CG",
                      wall_time_s=0.1, extra={})
    roof = {"bottlenecks": [{"severity": "warning",
                             "code": "roofline_stage",
                             "message": "level 2 restrict at 9% of HBM "
                                        "peak", "suggestion": "x"}]}
    comp = {"retrace_events": [{"fn": "f", "sig": "f32[8]",
                                "prior_sigs": 1}],
            "totals": {"compile_s": 0.09}}
    fs = diagnose(rep, roofline=roof, compile_stats=comp)
    codes = {f["code"] for f in fs}
    assert "roofline_stage" in codes and "retrace" in codes
    # PER-CALL compile time dominating a non-first call is a finding;
    # process-cumulative totals alone must NOT trip it (a warm solve
    # after one normal first-call compile is healthy)
    rep2 = SolveReport(10, 1e-8, solver="CG", wall_time_s=0.1)
    comp2 = {"retrace_events": [], "new_compile_s": 0.09}
    assert any(f["code"] == "compile_dominates"
               for f in diagnose(rep2, compile_stats=comp2))
    cumulative = {"retrace_events": [], "totals": {"compile_s": 9.0}}
    assert not any(f["code"] == "compile_dominates"
                   for f in diagnose(rep2, compile_stats=cumulative))


# ---------------------------------------------------------------------------
# profiler satellites: truncation visibility + counter support
# ---------------------------------------------------------------------------

def test_profiler_event_cap_is_loud():
    p = Profiler()
    p.MAX_EVENTS = 3                       # instance override
    with pytest.warns(UserWarning, match="event cap"):
        for _ in range(5):
            with p.scope("s"):
                pass
    assert p._events_dropped == 2
    trace = p.to_chrome_trace()
    assert trace["otherData"]["events_dropped"] == 2
    drop = [e for e in trace["traceEvents"] if e["ph"] == "i"]
    assert drop and drop[0]["args"]["dropped"] == 2
    # aggregate totals keep counting past the cap
    assert p.root.children["s"].count == 5


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

def test_cli_roofline_smoke():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               AMGCL_TPU_ROOFLINE_REPS="1")
    r = subprocess.run(
        [sys.executable, "-m", "amgcl_tpu.cli", "-n", "12",
         "-p", "solver.type=cg", "--roofline"],
        capture_output=True, text=True, timeout=420, cwd=_REPO, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Roofline" in r.stdout
    assert "xla-check" in r.stdout        # per-stage model-vs-XLA bytes
    assert "GB/s" in r.stdout
