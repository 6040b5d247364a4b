"""Storm open-loop load harness + saturation analytics (ISSUE 16): the
seeded arrival schedules (Poisson/burst/ramp determinism), the open-loop
sample accounting in ``telemetry/load.py`` (goodput excludes
sheds/timeouts/unhealthy, latency measured from the SCHEDULED arrival),
knee detection on ladder curves, the Perfetto storm timeline, the
/metrics scraper, the ``bench_storm`` round-over-round gate — and the
headline theorem: open-loop and closed-loop p99 DIVERGE under overload
(coordinated omission is real and the storm harness refuses to commit
it).

Everything here drives a pure-python stub queueing target (one worker,
deterministic service time, bounded queue) — no jax, no device — so the
protocol properties are tested exactly, not statistically.
"""

import concurrent.futures as _cf
import json
import os
import queue
import sys
import threading
import time
import types

import pytest

from amgcl_tpu import telemetry
from amgcl_tpu.faults import LoadShedError
from amgcl_tpu.serve import storm as S
from amgcl_tpu.telemetry import load as L

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench():
    sys.path.insert(0, _REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)
    return bench


# ===========================================================================
# arrival schedules: seeded determinism + shape
# ===========================================================================

PHASES = [S.poisson_phase(40.0, 1.0),
          S.burst_phase(5.0, 1.0, burst_every_s=0.25, burst_len=6),
          S.ramp_phase(10.0, 80.0, 1.0)]


def test_schedule_deterministic_and_ordered():
    """Same (phases, tenants, seed) -> byte-identical schedule; a
    different seed moves the arrivals; rows are time-sorted with dense
    rids."""
    a = S.build_schedule(PHASES, tenants=("t0", "t1"), seed=7)
    b = S.build_schedule(PHASES, tenants=("t0", "t1"), seed=7)
    assert a == b
    assert a != S.build_schedule(PHASES, tenants=("t0", "t1"), seed=8)
    ts = [r["t_s"] for r in a]
    assert ts == sorted(ts)
    assert [r["rid"] for r in a] == list(range(len(a)))
    assert {r["tenant"] for r in a} == {"t0", "t1"}
    assert {r["phase"] for r in a} == {"poisson", "burst", "ramp"}
    # phases lie back-to-back: every arrival inside the 3 s span
    assert 0.0 <= ts[0] and ts[-1] < S.schedule_duration_s(PHASES) == 3.0


def test_poisson_phase_mean_rate():
    """Seeded homogeneous Poisson arrivals land near rate*duration
    (deterministic given the seed, so the bound never flakes)."""
    rows = S.build_schedule([S.poisson_phase(200.0, 2.0)], seed=3)
    # E[N] = 400, sd = 20 — a 5-sigma band
    assert 300 <= len(rows) <= 500
    assert all(0.0 <= r["t_s"] < 2.0 for r in rows)
    assert all(r["rate_rps"] == 200.0 for r in rows)


def test_ramp_phase_density_and_rate_annotation():
    """An increasing ramp puts more arrivals in the second half
    (Lambda(2)-Lambda(1) = 77.5 vs Lambda(1) = 32.5 for 10->100 over
    2 s); the per-row rate annotation ramps monotonically with t; a
    DECREASING ramp terminates (finite total intensity)."""
    rows = S.build_schedule([S.ramp_phase(10.0, 100.0, 2.0)], seed=11)
    lo = [r for r in rows if r["t_s"] < 1.0]
    hi = [r for r in rows if r["t_s"] >= 1.0]
    assert len(hi) > 1.5 * len(lo)
    rates = [r["rate_rps"] for r in rows]
    assert rates == sorted(rates)
    assert rates[0] < 50.0 < rates[-1] <= 100.0
    down = S.build_schedule([S.ramp_phase(100.0, 10.0, 2.0)], seed=11)
    assert down and all(0.0 <= r["t_s"] < 2.0 for r in down)


def test_burst_phase_trains_are_deterministic():
    """The flash-crowd trains ride the Poisson background verbatim:
    burst_len arrivals 1 ms apart at every multiple of burst_every_s,
    independent of the seed."""
    phase = S.burst_phase(5.0, 2.0, burst_every_s=0.5, burst_len=6)
    rows = S.build_schedule([phase], seed=1)
    ts = {r["t_s"] for r in rows}
    for k in (1, 2, 3):          # trains at 0.5, 1.0, 1.5
        for j in range(6):
            assert round(k * 0.5 + j * 1e-3, 6) in ts
    assert len(rows) >= 18        # 3 trains + background


# ===========================================================================
# the open-loop sample accounting (telemetry/load.py)
# ===========================================================================

def _sample(rid, t, outcome, lat=None, tenant="t0", phase="poisson",
            spans=None):
    s = {"rid": rid, "tenant": tenant, "phase": phase, "rate_rps": 10.0,
         "t_sched_s": t, "t_submit_s": t, "lag_ms": 0.1,
         "outcome": outcome}
    if lat is not None:
        s["latency_ms"] = lat
        s["t_done_s"] = t + lat / 1e3
    if spans is not None:
        s["spans_ms"] = spans
    return s


def test_summarize_goodput_excludes_bad_outcomes():
    """goodput counts ONLY ok completions; sheds/timeouts/unhealthy/
    errors appear in their rate fields and in bad_frac; latency
    percentiles cover ok rows alone."""
    spans = {"queue": 2.0, "pad": 0.5, "compile": 0.0, "solve": 6.0,
             "sync": 1.5}
    samples = (
        [_sample(i, i * 0.1, "ok", lat=10.0 + i, spans=spans)
         for i in range(6)]
        + [_sample(6, 0.6, "shed", lat=0.2),
           _sample(7, 0.7, "timeout", lat=500.0),
           _sample(8, 0.8, "unhealthy", lat=20.0),
           _sample(9, 0.9, "error", lat=20.0)])
    out = L.summarize_samples(samples, duration_s=1.0)
    assert out["requests"] == 10
    assert out["outcomes"]["ok"] == 6
    assert out["offered_rps"] == 10.0
    assert out["shed_rate"] == 0.1 and out["timeout_rate"] == 0.1
    assert out["unhealthy_rate"] == 0.1 and out["error_rate"] == 0.1
    assert out["bad_frac"] == 0.4
    # goodput_rps / offered_rps: 6 good of 10 offered over the same
    # clock would be 0.6; the wall stretches past the schedule end so
    # the fraction sits at or under it
    assert 0 < out["goodput_frac"] <= 0.6
    assert out["latency_ms"]["count"] == 6
    assert out["latency_ms"]["max"] == 15.0   # the 500 ms timeout row
    #                                           never enters the ok set
    assert out["spans_ms"]["solve"] == 6.0
    assert abs(sum(out["span_share"].values()) - 1.0) < 1e-6
    assert out["span_share"]["solve"] == 0.6


def test_detect_knee_all_three_reasons_and_clean():
    """Each saturation criterion fires on the FIRST offending rung in
    offered-rate order, and max_sustainable_rps is the best goodput
    strictly below the knee."""
    def row(i, rate, p99, gf, qd=None):
        return {"rung": i, "offered_rps": rate, "p99_ms": p99,
                "goodput_frac": gf, "goodput_rps": rate * gf,
                "queue_depth_max": qd}
    clean = [row(0, 10, 5.0, 1.0), row(1, 20, 6.0, 0.99),
             row(2, 40, 8.0, 0.97)]
    k = L.detect_knee(clean, slo_p99_ms=50.0)
    assert not k["saturated"] and k["reason"] is None
    assert k["knee_offered_rps"] is None
    assert k["max_sustainable_rps"] == 40 * 0.97

    slo = clean[:2] + [row(2, 40, 80.0, 0.97)]
    k = L.detect_knee(slo, slo_p99_ms=50.0)
    assert k["saturated"] and k["reason"] == "p99_slo_breach"
    assert k["knee_offered_rps"] == 40 and k["knee_p99_ms"] == 80.0
    assert k["max_sustainable_rps"] == 20 * 0.99

    gp = clean[:2] + [row(2, 40, 8.0, 0.5)]
    k = L.detect_knee(gp)                      # no SLO set
    assert k["reason"] == "goodput_collapse"
    assert k["knee_rung"] == 2

    qd = [row(0, 10, 5.0, 1.0, qd=2), row(1, 20, 6.0, 0.99, qd=900)]
    k = L.detect_knee(qd, queue_depth_limit=100.0)
    assert k["reason"] == "queue_divergence"
    assert k["knee_offered_rps"] == 20
    assert k["max_sustainable_rps"] == 10.0


def test_build_record_schema_and_reference():
    """The bench_storm record body: schema pin, curve rows per rung,
    aggregate goodput accounting, and the reference row = LOWEST
    offered rate (the gate's p99 comparison point)."""
    spans = {"queue": 1.0, "pad": 0.2, "compile": 0.0, "solve": 4.0,
             "sync": 0.8}
    def rung(rate, n_ok, n_shed):
        samples = [_sample(i, i / rate, "ok", lat=8.0, spans=spans)
                   for i in range(n_ok)]
        samples += [_sample(n_ok + j, (n_ok + j) / rate, "shed",
                            lat=0.1) for j in range(n_shed)]
        return {"offered_rps": rate,
                "summary": L.summarize_samples(
                    samples, duration_s=(n_ok + n_shed) / rate),
                "gauges": [{"t_s": 0.1, "queue_depth": 3.0}]}
    rungs = [rung(40.0, 8, 8), rung(10.0, 10, 0)]   # unsorted on purpose
    rec = L.build_record(rungs, slo_p99_ms=100.0)
    assert rec["schema"] == L.STORM_SCHEMA == 1
    assert len(rec["curve"]) == 2
    assert rec["reference"]["offered_rps"] == 10.0
    assert rec["reference"]["p99_ms"] == 8.0
    assert rec["goodput"]["requests"] == 26
    assert rec["goodput"]["ok"] == 18
    assert rec["goodput"]["outcomes"]["shed"] == 8
    assert rec["knee"]["saturated"]            # rate-40 rung shed half
    assert rec["knee"]["reason"] == "goodput_collapse"
    assert rec["attribution"] and \
        rec["attribution"][0]["shares"]["solve"] > 0
    assert rec["gauges"]["rows"] == 2
    json.dumps(rec)                            # JSONL-clean


def test_storm_timeline_trace_shape():
    """Perfetto export: per-tenant thread tracks, complete events
    spanning scheduled arrival -> completion, instant markers for bad
    outcomes, counter tracks from the gauge series."""
    samples = [_sample(0, 0.1, "ok", lat=12.0, tenant="a"),
               _sample(1, 0.2, "shed", lat=0.1, tenant="b")]
    gauges = [{"t_s": 0.15, "queue_depth": 4.0}]
    tr = L.storm_timeline_trace(samples, gauges)
    evs = tr["traceEvents"]
    names = {e["ph"] for e in evs}
    assert {"M", "X", "i", "C"} <= names
    x = [e for e in evs if e["ph"] == "X"][0]
    assert x["ts"] == pytest.approx(0.1 * 1e6)
    assert x["dur"] == pytest.approx(12.0 * 1e3)
    meta = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert meta == {"storm/a", "storm/b"}
    c = [e for e in evs if e["ph"] == "C"][0]
    assert c["args"] == {"queue_depth": 4.0}


# ===========================================================================
# /metrics scraping
# ===========================================================================

PROM_PAGE = """\
# HELP amgcl_tpu_farm_queue_depth per-tenant backlog
# TYPE amgcl_tpu_farm_queue_depth gauge
amgcl_tpu_farm_queue_depth{tenant="a"} 3
amgcl_tpu_farm_queue_depth{tenant="b"} 4.5
amgcl_tpu_serve_inflight 2
amgcl_tpu_serve_requests_total 120
amgcl_tpu_serve_batch_fill 0.75
not a metric line
"""


def test_parse_prometheus_gauges_sums_label_variants():
    out = S.parse_prometheus_gauges(PROM_PAGE)
    assert out["queue_depth"] == 7.5      # tenants summed
    assert out["inflight"] == 2.0
    assert out["requests_total"] == 120.0
    assert set(out) == {"queue_depth", "inflight", "requests_total"}


def test_scraper_counts_errors_instead_of_swallowing():
    """An unreachable /metrics endpoint never fails the storm, but the
    failures are COUNTED on the scraper (the swallowed-worker-exception
    lint contract: broad handlers in thread targets must do real
    work)."""
    lock = threading.Lock()
    rows = []
    sc = S._Scraper("http://127.0.0.1:9/metrics", 0.02,
                    time.perf_counter(), lock, rows).start()
    time.sleep(0.15)
    sc.stop()
    assert sc.errors > 0
    assert sc.last_error
    assert rows == []


# ===========================================================================
# the open-loop run against a stub queueing target
# ===========================================================================

class _StubTarget:
    """One worker, deterministic service time, bounded queue — an exact
    M/D/1/K system the storm protocol properties are provable on."""

    def __init__(self, service_s=0.008, qmax=16, healthy=True):
        self.service_s = service_s
        self.healthy = healthy
        self._q = queue.Queue(maxsize=qmax)
        self._t = threading.Thread(target=self._worker, daemon=True)
        self._t.start()

    def submit(self, tenant, rhs):
        fut = _cf.Future()
        self._q.put_nowait((fut, rhs))     # queue.Full -> shed
        return fut

    def _worker(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            fut, rhs = item
            time.sleep(self.service_s)
            rep = types.SimpleNamespace(
                health={"ok": self.healthy, "flags": []
                        if self.healthy else ["stub"]},
                serve={"queue_ms": 1.0, "pad_ms": 0.1,
                       "compile_ms": 0.0,
                       "solve_ms": self.service_s * 1e3,
                       "sync_ms": 0.2,
                       "latency_ms": self.service_s * 1e3 + 1.3})
            fut.set_result((rhs, rep))

    def close(self):
        self._q.put(None)
        self._t.join(timeout=5.0)


def test_open_loop_vs_closed_loop_p99_diverge_under_overload():
    """THE theorem this harness exists for: drive the same overloaded
    target (capacity ~125 rps) both ways. The closed-loop protocol
    submits-waits-submits, so its per-request latency stays ~= the
    service time no matter how overloaded the system is — coordinated
    omission. The open-loop storm charges queueing from the SCHEDULED
    arrival and its p99 explodes. They must diverge by >= 3x."""
    tgt = _StubTarget(service_s=0.008, qmax=16)
    try:
        # closed loop: one at a time, latency measured submit->done
        closed = []
        for _ in range(30):
            t0 = time.perf_counter()
            tgt.submit("t0", b"x").result(timeout=10)
            closed.append((time.perf_counter() - t0) * 1e3)
        closed.sort()
        closed_p99 = closed[int(0.99 * (len(closed) - 1))]

        # open loop: offered 300 rps >> capacity, same target
        sched = S.build_schedule([S.poisson_phase(300.0, 1.0)], seed=5)
        res = S.run_storm(tgt, sched, lambda tenant, rid: b"x",
                          drain_timeout_s=10.0, scrape_every_s=0.0,
                          emit_event=False)
    finally:
        tgt.close()
    summ = res["summary"]
    assert summ["outcomes"].get("pending", 0) == 0
    assert summ["outcomes"]["ok"] > 20
    assert summ["shed_rate"] > 0.2        # the bounded queue shed load
    open_p99 = summ["latency_ms"]["p99"]
    assert open_p99 > 3 * closed_p99, (open_p99, closed_p99)
    # and goodput saturates near capacity, far under the offered rate
    assert summ["goodput_rps"] < 0.75 * summ["offered_rps"]


def test_run_storm_outcomes_spans_and_event(tmp_path):
    """A gentle storm on a healthy stub: all ok, spans copied off the
    reports, latency from the scheduled arrival, one `storm` JSONL
    event with the headline numbers."""
    out = tmp_path / "storm.jsonl"
    telemetry.set_default_sink(telemetry.JsonlSink(str(out)))
    tgt = _StubTarget(service_s=0.002, qmax=64)
    try:
        sched = S.build_schedule([S.poisson_phase(50.0, 0.5)],
                                 tenants=("a", "b"), seed=2)
        res = S.run_storm(tgt, sched, lambda tenant, rid: b"x",
                          drain_timeout_s=10.0, scrape_every_s=0.0,
                          label="gentle")
    finally:
        tgt.close()
        telemetry.set_default_sink(telemetry.NullSink())
    summ = res["summary"]
    assert summ["outcomes"] == {"ok": summ["requests"]}
    assert summ["goodput_frac"] > 0.5
    ok_rows = [s for s in res["samples"] if s["outcome"] == "ok"]
    assert all(s["spans_ms"]["solve"] == 2.0 for s in ok_rows)
    assert all(s["latency_ms"] >= 0 for s in ok_rows)
    recs = [json.loads(ln) for ln in open(out)]
    ev = [r for r in recs if r.get("event") == "storm"]
    assert len(ev) == 1 and ev[0]["label"] == "gentle"
    assert ev[0]["requests"] == summ["requests"]
    assert ev[0]["p99_ms"] == summ["latency_ms"]["p99"]
    assert ev[0]["shed_rate"] == 0.0


def test_unhealthy_solves_excluded_from_goodput():
    tgt = _StubTarget(service_s=0.001, qmax=64, healthy=False)
    try:
        sched = S.build_schedule([S.poisson_phase(40.0, 0.4)], seed=4)
        res = S.run_storm(tgt, sched, lambda tenant, rid: b"x",
                          drain_timeout_s=10.0, scrape_every_s=0.0,
                          emit_event=False)
    finally:
        tgt.close()
    summ = res["summary"]
    assert summ["outcomes"] == {"unhealthy": summ["requests"]}
    assert summ["unhealthy_rate"] == 1.0
    assert summ["goodput_rps"] == 0.0
    assert "latency_ms" not in summ       # no ok rows, no percentiles


def test_classify_exc_classes():
    class RequestTimeout(Exception):
        pass
    assert S._classify_exc(queue.Full()) == "shed"
    assert S._classify_exc(LoadShedError("t0", 1, 2)) == "shed"
    assert S._classify_exc(TimeoutError()) == "timeout"
    assert S._classify_exc(RequestTimeout()) == "timeout"
    assert S._classify_exc(ValueError("boom")) == "error"


def test_ladder_to_knee_on_stub():
    """End-to-end analytics on the stub: a 3-rung ladder crossing the
    stub's ~125 rps capacity produces a curve whose knee lands at an
    overloaded rung, with max_sustainable_rps below capacity."""
    tgt = _StubTarget(service_s=0.008, qmax=16)
    try:
        rungs = S.run_ladder(tgt, (20.0, 60.0, 400.0), 0.8,
                             lambda tenant, rid: b"x", seed=9,
                             drain_timeout_s=10.0, scrape_every_s=0.0,
                             emit_events=False)
    finally:
        tgt.close()
    rec = L.build_record(rungs)
    assert [r["offered_rps"] for r in rec["curve"]] == [20.0, 60.0,
                                                        400.0]
    assert rec["knee"]["saturated"]
    assert rec["knee"]["knee_offered_rps"] == 400.0
    assert rec["knee"]["max_sustainable_rps"] is not None
    assert rec["knee"]["max_sustainable_rps"] < 130.0
    assert rec["reference"]["offered_rps"] == 20.0


def test_armed_fault_plan_swaps_and_restores_env():
    key = "AMGCL_TPU_FAULT_PLAN"
    prev = os.environ.pop(key, None)
    try:
        with S.armed_fault_plan("serve_timeout_storm:p=1"):
            assert os.environ[key] == "serve_timeout_storm:p=1"
        assert key not in os.environ
        os.environ[key] = "outer"
        with S.armed_fault_plan("inner"):
            assert os.environ[key] == "inner"
        assert os.environ[key] == "outer"
        with S.armed_fault_plan(None):
            assert os.environ[key] == "outer"   # no-op when unset
    finally:
        os.environ.pop(key, None)
        if prev is not None:
            os.environ[key] = prev


# ===========================================================================
# the storm gate (bench.py)
# ===========================================================================

def _storm_rec(max_rps=100.0, ref_p99=20.0, ref_rps=10.0,
               platform="cpu"):
    return {"event": "bench_storm", "device_platform": platform,
            "record": {"schema": 1,
                       "knee": {"max_sustainable_rps": max_rps},
                       "reference": {"offered_rps": ref_rps,
                                     "p99_ms": ref_p99}}}


TOL = {"rate": 0.7, "p99": 1.5}


def test_storm_gate_clean_pass():
    bench = _bench()
    ok, checks = bench.run_storm_gate(_storm_rec(), _storm_rec(),
                                      tol=TOL)
    assert ok
    assert [c["status"] for c in checks] == ["ok", "ok"]
    assert [c["check"] for c in checks] == ["storm_max_rps",
                                            "storm_ref_p99"]


def test_storm_gate_fails_on_rate_and_p99_regressions():
    bench = _bench()
    base = _storm_rec(max_rps=100.0, ref_p99=20.0)
    ok, checks = bench.run_storm_gate(_storm_rec(max_rps=50.0), base,
                                      tol=TOL)
    assert not ok
    by = {c["check"]: c for c in checks}
    assert by["storm_max_rps"]["status"] == "regression"
    assert by["storm_max_rps"]["limit"] == 70.0
    ok, checks = bench.run_storm_gate(_storm_rec(ref_p99=45.0), base,
                                      tol=TOL)
    assert not ok
    by = {c["check"]: c for c in checks}
    assert by["storm_ref_p99"]["status"] == "regression"
    assert by["storm_ref_p99"]["limit"] == 30.0
    # riding the edge is still a pass (>= floor, <= ceiling)
    ok, _ = bench.run_storm_gate(
        _storm_rec(max_rps=70.0, ref_p99=30.0), base, tol=TOL)
    assert ok


def test_storm_gate_skips():
    """Platform mismatch skips every ratio; a recalibrated reference
    rate skips the p99 check only; AMGCL_TPU_GATE_STORM=0 disables."""
    bench = _bench()
    ok, checks = bench.run_storm_gate(
        _storm_rec(max_rps=1.0, ref_p99=9999.0, platform="cpu"),
        _storm_rec(platform="tpu"), tol=TOL)
    assert ok
    assert all(c["status"] == "skipped" for c in checks)
    assert all("platform_mismatch" in c["reason"] for c in checks)
    ok, checks = bench.run_storm_gate(
        _storm_rec(ref_p99=9999.0, ref_rps=40.0), _storm_rec(),
        tol=TOL)
    assert ok                      # p99 blew up, but at a different rate
    by = {c["check"]: c for c in checks}
    assert by["storm_max_rps"]["status"] == "ok"
    assert by["storm_ref_p99"]["status"] == "skipped"
    assert "reference_rate_mismatch" in by["storm_ref_p99"]["reason"]
    ok, checks = bench.run_storm_gate(
        _storm_rec(max_rps=0.001), _storm_rec(),
        tol={"rate": 0.0, "p99": 1.5})
    assert ok and checks[0]["status"] == "skipped"
    assert "disabled" in checks[0]["reason"]


def test_storm_gate_record_statuses(tmp_path, monkeypatch):
    """The --gate/--check sub-record contract: None when unused,
    no_candidate / no_baseline markers, ok=False + failed rows on a
    real regression."""
    bench = _bench()
    cand_path = tmp_path / "cand.json"
    monkeypatch.setenv("AMGCL_TPU_GATE_STORM_CANDIDATE", str(cand_path))
    monkeypatch.setattr(bench, "_storm_baseline", lambda: None)
    assert bench.storm_gate_record() is None        # unused: no files
    base = dict(_storm_rec(), path="STORM_r1.json")
    monkeypatch.setattr(bench, "_storm_baseline", lambda: base)
    rec = bench.storm_gate_record()
    assert rec["status"] == "no_candidate" and rec["ok"]
    cand_path.write_text(json.dumps(_storm_rec(max_rps=10.0)))
    monkeypatch.setattr(bench, "_storm_baseline", lambda: None)
    rec = bench.storm_gate_record()
    assert rec["status"] == "no_baseline" and rec["ok"]
    monkeypatch.setattr(bench, "_storm_baseline", lambda: base)
    rec = bench.storm_gate_record()
    assert not rec["ok"]
    assert rec["baseline"] == "STORM_r1.json"
    assert rec["failed"][0]["check"] == "storm_max_rps"
    assert rec["failed"][0]["candidate"] == 10.0
    assert rec["failed"][0]["baseline"] == 100.0


def test_storm_history_and_trend_fields(tmp_path):
    """STORM_r*.json round files join bench --trend through
    metrics.storm_history + STORM_TREND_FIELDS."""
    from amgcl_tpu.telemetry import metrics as m
    for i, rps in ((1, 80.0), (2, 120.0)):
        (tmp_path / ("STORM_r%d.json" % i)).write_text(json.dumps(
            dict(_storm_rec(max_rps=rps),
                 record=dict(_storm_rec(max_rps=rps)["record"],
                             goodput={"good_frac": 0.9,
                                      "requests": 100}))))
    (tmp_path / "STORM_LATEST.json").write_text("{}")   # not a round
    hist = m.storm_history(str(tmp_path))
    assert [h["round"] for h in hist] == [1, 2]
    rows = m.trend(hist, m.STORM_TREND_FIELDS)
    assert [r["max_rps"] for r in rows] == [80.0, 120.0]
    assert all(r["good_frac"] == 0.9 for r in rows)
