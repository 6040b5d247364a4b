"""Memory observatory (ISSUE 18): measured-vs-ledger joins per device
format, ownership attribution through eviction, the leak-cycle
selftest and its negative injection, RESOURCE_EXHAUSTED classification
into the typed AllocationError hierarchy, OOM flight forensics
(timeline + top-owner table in the bundle manifest), the doctor
``memory=`` fold, measured farm headroom, and the live gauges."""

import glob
import json
import os

import numpy as np
import jax.numpy as jnp
import pytest

from amgcl_tpu import faults
from amgcl_tpu.faults import inject
from amgcl_tpu.models.amg import AMG, AMGParams
from amgcl_tpu.models.make_solver import make_solver
from amgcl_tpu.solver.cg import CG
from amgcl_tpu.telemetry import memwatch
from amgcl_tpu.telemetry import flight
from amgcl_tpu.telemetry.health import diagnose
from amgcl_tpu.utils.sample_problem import poisson3d

KNOBS = ("AMGCL_TPU_MEMWATCH", "AMGCL_TPU_MEMWATCH_INTERVAL_MS",
         "AMGCL_TPU_MEMWATCH_TIMELINE", "AMGCL_TPU_MEMWATCH_TOL",
         "AMGCL_TPU_MEMWATCH_LEAK_BYTES", "AMGCL_TPU_FARM_HEADROOM",
         "AMGCL_TPU_FAULT_PLAN", "AMGCL_TPU_FLIGHT_DIR")


@pytest.fixture(autouse=True)
def _fresh_memwatch():
    saved = {k: os.environ.get(k) for k in KNOBS}
    memwatch._reset_for_tests()
    flight._reset_for_tests()
    inject._reset_for_tests()
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    memwatch._reset_for_tests()
    flight._reset_for_tests()
    inject._reset_for_tests()


def _amg(fmt="auto", n=8, **kw):
    A, _ = poisson3d(n)
    kw.setdefault("coarse_enough", 20)
    kw.setdefault("max_levels", 3)
    return AMG(A, AMGParams(dtype=jnp.float32, matrix_format=fmt, **kw))


# ---------------------------------------------------------------------------
# measured-vs-ledger join, per device format
# ---------------------------------------------------------------------------

_EXPECT = {"dia": "DiaMatrix", "ell": "EllMatrix",
           "dense": "DenseMatrix", "well": "WindowedEllMatrix"}


@pytest.mark.parametrize("fmt", sorted(_EXPECT))
def test_join_within_tolerance_per_format(fmt):
    """AMG.bytes() (the analytic ledger) vs the live-array measurement
    agrees within the declared tolerance for every device format —
    the number every admission/eviction decision trusts."""
    amg = _amg(fmt)
    assert type(amg.hierarchy.levels[0].A).__name__ == _EXPECT[fmt]
    tol = memwatch.declared_tolerance()
    measured = memwatch.measured_tree_bytes(amg.hierarchy)
    assert measured > 0
    assert abs(measured - amg.bytes()) <= tol * amg.bytes()
    rep = amg.memory_report()
    assert rep["provenance"] == "measured" and rep["resident"]
    assert len(rep["levels"]) >= 2
    assert abs(rep["drift_ratio"] - 1.0) <= tol
    for row in rep["levels"]:
        assert abs(row["drift_ratio"] - 1.0) <= tol, row
        assert row["slots"].get("A", 0) > 0
    # a clean join raises no doctor findings (just the healthy row)
    assert [f for f in diagnose(None, memory=rep)
            if f["code"] != "healthy"] == []


def test_release_device_zeroes_measured_owner():
    amg = _amg("dia")
    name = memwatch.register_owner("hierarchy", amg)
    assert name is not None
    row = next(r for r in memwatch.owner_table() if r["owner"] == name)
    assert row["bytes_measured"] > 0 and row["drift_ratio"] == 1.0
    amg.release_device()
    assert memwatch.measured_tree_bytes(amg.hierarchy) == 0
    row = next(r for r in memwatch.owner_table() if r["owner"] == name)
    assert row["bytes_measured"] == 0
    rep = amg.memory_report()
    assert rep["resident"] is False and rep["total_measured"] == 0
    # the owner row dies with its object (weakref registry)
    del amg, row
    assert all(r["owner"] != name for r in memwatch.owner_table())


def test_owner_table_census_remainder():
    """On the CPU census the table closes: attributed rows plus the
    ``unattributed`` remainder account for every live byte."""
    amg = _amg("dia")
    memwatch.register_owner("hierarchy", amg)
    sample = memwatch.device_sample()
    assert sample["source"] == "census"
    rows = memwatch.owner_table(sample)
    un = next(r for r in rows if r["owner"] == "unattributed")
    attributed = sum(r["bytes_measured"] for r in rows
                     if r["owner"] != "unattributed")
    assert attributed + un["bytes_measured"] >= sample["bytes_in_use"]


# ---------------------------------------------------------------------------
# timeline, kill switch, Perfetto export
# ---------------------------------------------------------------------------

def test_timeline_bounded_and_kill_switch(monkeypatch):
    monkeypatch.setenv("AMGCL_TPU_MEMWATCH_TIMELINE", "16")
    for i in range(40):
        assert memwatch.snapshot("unit.test", i=i) is not None
    rows = memwatch.timeline()
    assert len(rows) == 16 and rows[-1]["i"] == 39
    trace = memwatch.to_chrome_trace()
    phases = {e["ph"] for e in trace["traceEvents"]}
    assert {"C", "i", "M"} <= phases
    monkeypatch.setenv("AMGCL_TPU_MEMWATCH", "0")
    assert memwatch.snapshot("unit.test") is None
    assert memwatch.register_owner("hierarchy", object()) is None


def test_sampler_thread_fills_timeline():
    assert memwatch.start_sampler(0.005)
    try:
        import time
        deadline = time.perf_counter() + 2.0
        while time.perf_counter() < deadline:
            if any(r["phase"] == "sampler" for r in memwatch.timeline()):
                break
            time.sleep(0.01)
    finally:
        memwatch.stop_sampler()
    ticks = [r for r in memwatch.timeline() if r["phase"] == "sampler"]
    assert ticks and ticks[0]["bytes_in_use"] is not None


# ---------------------------------------------------------------------------
# doctor findings (telemetry.diagnose(memory=...))
# ---------------------------------------------------------------------------

def test_memory_findings_drift_leak_unattributed():
    codes = [f["code"] for f in memwatch.memory_findings(
        {"drift_ratio": 2.0, "tolerance": 0.25, "leaked_bytes": 4096,
         "owners": [{"owner": "unattributed", "bytes_measured": 900},
                    {"owner": "hierarchy:1", "bytes_measured": 100}]})]
    assert codes == ["mem_drift", "mem_leak", "mem_unattributed"]
    assert memwatch.memory_findings({"drift_ratio": 1.01,
                                     "leaked_bytes": 0}) == []
    sev = {f["code"]: f["severity"]
           for f in diagnose(None, memory={"drift_ratio": 1.0,
                                           "leaked_bytes": 1})}
    assert sev["mem_leak"] == "critical"


# ---------------------------------------------------------------------------
# RESOURCE_EXHAUSTED classification -> typed AllocationError
# ---------------------------------------------------------------------------

def test_is_resource_exhausted_classification():
    class XlaRuntimeError(Exception):
        pass

    assert faults.is_resource_exhausted(
        XlaRuntimeError("RESOURCE_EXHAUSTED: Out of memory ..."))
    assert faults.is_resource_exhausted(
        XlaRuntimeError("Failed to allocate 12884901888 bytes"))
    assert faults.is_resource_exhausted(
        RuntimeError("RESOURCE_EXHAUSTED while compiling"))
    assert not faults.is_resource_exhausted(
        XlaRuntimeError("INVALID_ARGUMENT: shape mismatch"))
    assert not faults.is_resource_exhausted(ValueError("nope"))
    assert not faults.is_resource_exhausted(None)
    # typed faults never re-classify (no double wrapping)
    assert not faults.is_resource_exhausted(
        faults.AllocationError("RESOURCE_EXHAUSTED"))
    # the hierarchy: admission refusals ARE allocation errors
    assert issubclass(faults.AdmissionError, faults.AllocationError)
    assert issubclass(faults.AllocationError, faults.FaultError)


def test_dispatch_oom_raises_typed_with_forensics(tmp_path, monkeypatch):
    """A backend RESOURCE_EXHAUSTED escaping the compiled entry comes
    back as faults.AllocationError, and the flight bundle embeds the
    memory timeline + top-owner table."""
    monkeypatch.setenv("AMGCL_TPU_FLIGHT_DIR", str(tmp_path))
    flight._reset_for_tests()
    A, rhs = poisson3d(8)
    b = make_solver(A, AMGParams(dtype=jnp.float32, coarse_enough=200),
                    CG(maxiter=50, tol=1e-6))
    b(rhs.astype(np.float32))        # warm: populates b._compiled

    class XlaRuntimeError(Exception):
        pass

    def boom(*a, **kw):
        raise XlaRuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 8589934592 "
            "bytes")

    monkeypatch.setattr(b, "_compiled", boom)
    with pytest.raises(faults.AllocationError) as ei:
        b(rhs.astype(np.float32))
    assert "measured bytes" in str(ei.value)
    assert isinstance(ei.value.__cause__, XlaRuntimeError)
    mans = glob.glob(str(tmp_path / "*" / "manifest.json"))
    assert mans, "no flight bundle dumped"
    man = json.load(open(mans[0]))
    assert man["reason"] == "allocation_failure"
    tags = man["tags"]
    assert tags["seam"] == "solve.dispatch"
    assert tags["memory_owners"] and tags["memory_timeline"]
    assert tags["memory_timeline"][-1]["phase"] == "allocation_failure"
    # a non-OOM failure still raises untyped (no blanket rewrap)
    monkeypatch.setattr(
        b, "_compiled",
        lambda *a, **kw: (_ for _ in ()).throw(ValueError("bad")))
    with pytest.raises(ValueError):
        b(rhs.astype(np.float32))


def test_farm_admission_refusal_typed_with_forensics(tmp_path,
                                                     monkeypatch):
    """The injected ``alloc.farm`` refusal surfaces as the typed
    AllocationError (AdmissionError leg) and trips the same OOM
    forensics bundle."""
    from amgcl_tpu.serve.farm import SolverFarm
    monkeypatch.setenv("AMGCL_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("AMGCL_TPU_FAULT_PLAN", json.dumps(
        [{"site": "alloc.farm", "count": 1}]))
    flight._reset_for_tests()
    inject._reset_for_tests()
    A, _ = poisson3d(8)
    farm = SolverFarm(max_bytes=1, metrics_port=-1)
    try:
        with pytest.raises(faults.AllocationError):
            farm.register("t0", A,
                          precond=AMGParams(dtype=jnp.float32,
                                            coarse_enough=200))
    finally:
        farm.close()
    mans = [m for m in glob.glob(str(tmp_path / "*" / "manifest.json"))
            if json.load(open(m))["reason"] == "allocation_failure"]
    assert mans, "no allocation_failure bundle dumped"
    tags = json.load(open(mans[0]))["tags"]
    assert tags["seam"] == "farm.register" and tags["tenant"] == "t0"
    assert "pool_used" in tags and "pool_total" in tags
    assert isinstance(tags["memory_timeline"], list)
    assert isinstance(tags["memory_owners"], list)


# ---------------------------------------------------------------------------
# per-solve measured resources + measured farm headroom
# ---------------------------------------------------------------------------

def test_solve_report_carries_measured_bytes():
    A, rhs = poisson3d(8)
    b = make_solver(A, AMGParams(dtype=jnp.float32, coarse_enough=200),
                    CG(maxiter=50, tol=1e-6))
    _, rep = b(rhs.astype(np.float32))
    bm = rep.resources["bytes_measured"]
    assert bm["provenance"] == "measured"
    assert bm["hierarchy"] > 0 and bm["total"] >= bm["hierarchy"]
    assert bm["device"]["source"] == "census"
    assert any(r["phase"] == "solve" for r in memwatch.timeline())


def test_farm_headroom_measured_mode(monkeypatch):
    """AMGCL_TPU_FARM_HEADROOM=measured charges max(measured, model)
    so a drifting model can never silently over-admit."""
    from amgcl_tpu.serve.farm import SolverFarm
    monkeypatch.setenv("AMGCL_TPU_FARM_HEADROOM", "measured")
    A, _ = poisson3d(8)
    farm = SolverFarm(max_bytes=0, metrics_port=-1)
    try:
        farm.register("t0", A, precond=AMGParams(dtype=jnp.float32,
                                                 coarse_enough=20,
                                                 max_levels=3))
        assert farm._headroom_mode == "measured"
        ten = farm.tenants["t0"]
        hint = farm._bytes_hint[ten.entry.uid]
        measured = memwatch.measured_tree_bytes(
            ten.entry.obj.precond.hierarchy)
        model = ten.entry.obj.precond.bytes()
        assert hint >= measured and hint >= min(measured, model)
    finally:
        farm.close()


# ---------------------------------------------------------------------------
# the leak-cycle selftest (the bench --check record) + live gauges
# ---------------------------------------------------------------------------

def test_selftest_clean_and_leak_injection():
    rec = memwatch.selftest(cycles=1)
    assert rec["ok"], rec
    assert rec["leaked_bytes"] == 0
    assert abs(rec["drift_ratio"] - 1.0) <= rec["tolerance"]
    assert {c["check"] for c in rec["checks"]} == {
        "join_within_tolerance", "evict_zeroes_owner",
        "cycle_returns_to_baseline"}
    json.dumps(rec)                  # JSONL-sink clean
    # the negative injection: a deliberately pinned buffer per cycle
    # must flip the record (what proves the bench gate can trip)
    memwatch._reset_for_tests()
    bad = memwatch.selftest(cycles=1, leak_bytes=2_000_000)
    assert not bad["ok"] and bad["leaked_bytes"] >= 2_000_000
    assert any(f["code"] == "mem_leak" for f in bad["findings"])


def test_publish_memwatch_gauges():
    from amgcl_tpu.telemetry import live
    amg = _amg("dia")
    memwatch.register_owner("hierarchy", amg, name="hierarchy:test")
    reg = live.LiveRegistry()
    live.publish_memwatch_gauges(reg)
    assert reg.get("memwatch_bytes_in_use") > 0
    assert reg.get("memwatch_owner_bytes", owner="hierarchy:test") > 0
    assert reg.get("memwatch_unattributed_bytes") is not None
