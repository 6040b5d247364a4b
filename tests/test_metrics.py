"""Fleet metric rollups (ISSUE 4): percentile math, dotted-path
extraction over heterogeneous records, the cross-round bench trend over
a BENCH_r01..r05.json history written per test (missing-field tolerance
for pre-ledger rounds), Prometheus export, the JSONL sink size-capped
rotation satellite, and the bench.py --trend surface."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from amgcl_tpu.telemetry import metrics as m
from amgcl_tpu.telemetry.sink import JsonlSink

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# percentiles / rollups / extraction
# ---------------------------------------------------------------------------

def test_percentile_interpolates():
    vals = [1.0, 2.0, 3.0, 4.0]
    assert m.percentile(vals, 50) == 2.5
    assert m.percentile(vals, 0) == 1.0
    assert m.percentile(vals, 100) == 4.0
    assert m.percentile([7.0], 99) == 7.0
    assert m.percentile([], 50) is None
    assert m.percentile([float("nan"), 5.0], 50) == 5.0


def test_rollup_summary():
    r = m.rollup([3, 1, 2, None, float("inf"), "x"])
    assert r["count"] == 3 and r["min"] == 1 and r["max"] == 3
    assert r["p50"] == 2 and r["last"] == 2.0
    assert m.rollup(["a", None]) is None
    assert m.rollup([True, True]) is None    # bools are not metrics


def test_extract_dotted_paths():
    rec = {"a": {"b": {"c": 7}}, "x": 1}
    assert m.extract(rec, "a.b.c") == 7
    assert m.extract(rec, "a.b.missing") is None
    assert m.extract(rec, "x.y") is None
    assert m.extract({}, "a") is None


# ---------------------------------------------------------------------------
# bench history trend (BENCH_r*.json rounds)
# ---------------------------------------------------------------------------

def write_history(root):
    """Five driver-wrapped bench rounds in ``root``: r01/r02 produced no
    value, r03..r05 predate the ledger/compile/roofline fields."""
    for rnd in (1, 2):
        parsed = {"metric": "poisson3d_128_sa_cg_spai0_solve_time",
                  "value": None, "unit": "s", "vs_baseline": None,
                  "error": "no measurement"}
        with open(os.path.join(root, "BENCH_r%02d.json" % rnd), "w") as f:
            json.dump({"n": rnd, "rc": 0, "parsed": parsed}, f)
    for rnd, solve in ((3, 0.21), (4, 0.095), (5, 0.069)):
        parsed = {"metric": "poisson3d_128_sa_cg_spai0_solve_time",
                  "value": solve, "unit": "s", "vs_baseline": 0.9 / solve,
                  "iters": 13, "setup_s": 15.0 + rnd, "gen_s": 2.0,
                  "achieved_gbps": 100.0 + rnd}
        with open(os.path.join(root, "BENCH_r%02d.json" % rnd), "w") as f:
            json.dump({"n": rnd, "rc": 0, "parsed": parsed}, f)
    return str(root)


@pytest.fixture
def history(tmp_path):
    return write_history(tmp_path)


def test_bench_history_loads_all_rounds(history):
    hist = m.bench_history(history)
    rounds = [h["round"] for h in hist]
    assert rounds == sorted(rounds)
    assert set(rounds) >= {1, 2, 3, 4, 5}


def test_trend_tolerates_pre_ledger_records(history):
    """r01/r02 never produced a value and r03..r05 predate the
    ledger/compile/roofline fields — every round still renders, with
    gaps instead of errors."""
    rows = m.trend(m.bench_history(history))
    by_round = {r["round"]: r for r in rows}
    assert by_round[1]["solve_s"] is None and "error" in by_round[1]
    for rnd in (3, 4, 5):
        assert by_round[rnd]["solve_s"] > 0
        assert by_round[rnd]["iters"] == 13       # monotone across rounds
        assert by_round[rnd]["ledger_bytes"] is None   # pre-ledger
        assert by_round[rnd]["compile_s"] is None      # pre-watch
    txt = m.format_trend(rows)
    assert "round" in txt and "-" in txt
    for rnd in (1, 2, 3, 4, 5):
        assert str(rnd) in txt


def test_trend_rollups_and_prometheus(history):
    rows = m.trend(m.bench_history(history))
    roll = m.trend_rollups(rows)
    assert roll["solve_s"]["count"] >= 3
    assert roll["iters"]["p50"] == 13
    text = m.prometheus_text(roll)
    assert '# TYPE amgcl_tpu_solve_s summary' in text
    assert 'amgcl_tpu_solve_s{quantile="0.5"}' in text
    assert text.endswith("\n")
    # names sanitize to the prometheus charset
    bad = m.prometheus_text({"a.b/c": {"count": 1, "min": 0, "max": 1,
                                       "p50": 0.5, "p90": 1, "p99": 1,
                                       "mean": 0.5, "last": 1}})
    assert "amgcl_tpu_a_b_c" in bad


def test_rollup_events_groups_by_event():
    recs = [{"event": "solve", "iters": 10, "wall_time_s": 0.5},
            {"event": "solve", "iters": 20, "wall_time_s": 1.5},
            {"event": "doctor"},
            {"event": "solve", "iters": 30, "wall_time_s": 2.5,
             "resources": {"roofline": {"gbps": 7.0}}}]
    out = m.rollup_events(recs)
    assert out["solve.iters"]["count"] == 3
    assert out["solve.iters"]["p50"] == 20
    assert out["solve.solve_time_s"]["max"] == 2.5
    assert out["solve.achieved_gbps"]["count"] == 1


def test_iter_jsonl_merges_rotation_and_skips_torn(tmp_path):
    base = str(tmp_path / "out.jsonl")
    with open(base + ".1", "w") as f:
        f.write('{"i": 1}\n{"i": 2}\n')
    with open(base, "w") as f:
        f.write('{"i": 3}\n{"i": 4, "torn...\n')
    recs = m.iter_jsonl(base)
    assert [r["i"] for r in recs] == [1, 2, 3]
    assert m.iter_jsonl(str(tmp_path / "missing.jsonl")) == []


# ---------------------------------------------------------------------------
# sink rotation satellite (AMGCL_TPU_TELEMETRY_MAX_BYTES)
# ---------------------------------------------------------------------------

def test_sink_rotates_at_cap(tmp_path):
    path = str(tmp_path / "out.jsonl")
    sink = JsonlSink(path, max_bytes=300)
    for i in range(20):
        sink.emit(event="t", i=i)
    assert os.path.exists(path + ".1")
    # base file restarted below the cap + one record's slack
    assert os.path.getsize(path) < 300 + 200
    # no record was split across the rotation: both files parse line-wise
    seen = []
    for p in (path + ".1", path):
        with open(p) as f:
            for line in f:
                seen.append(json.loads(line)["i"])
    assert seen == sorted(seen)           # order preserved across files
    assert seen[-1] == 19


def test_sink_rotation_env_default(tmp_path, monkeypatch):
    monkeypatch.setenv("AMGCL_TPU_TELEMETRY_MAX_BYTES", "250")
    path = str(tmp_path / "env.jsonl")
    sink = JsonlSink(path)                # picks the env cap up
    assert sink.max_bytes == 250
    for i in range(20):
        sink.emit(event="t", i=i)
    assert os.path.exists(path + ".1")
    monkeypatch.setenv("AMGCL_TPU_TELEMETRY_MAX_BYTES", "nonsense")
    assert JsonlSink(str(tmp_path / "e2.jsonl")).max_bytes == 0


def test_sink_unbounded_without_cap(tmp_path):
    path = str(tmp_path / "u.jsonl")
    sink = JsonlSink(path)
    for i in range(10):
        sink.emit(event="t", i=i)
    assert not os.path.exists(path + ".1")


# ---------------------------------------------------------------------------
# bench.py --trend surface
# ---------------------------------------------------------------------------

def test_bench_trend_cli(tmp_path):
    """bench.py reads its history beside itself: run a copy of it next to
    the package, in a directory that holds the written rounds."""
    root = write_history(tmp_path)
    shutil.copy(os.path.join(_REPO, "bench.py"), root)
    os.symlink(os.path.join(_REPO, "amgcl_tpu"),
               os.path.join(root, "amgcl_tpu"))
    prom = str(tmp_path / "prom.txt")
    r = subprocess.run(
        [sys.executable, "bench.py", "--trend", "--prom", prom],
        capture_output=True, text=True, timeout=120, cwd=root)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "round" in r.stdout
    last = [ln for ln in r.stdout.splitlines() if ln.startswith("{")][-1]
    rec = json.loads(last)
    assert rec["event"] == "bench_trend"
    assert len(rec["rows"]) >= 5
    assert rec["rollups"]["solve_s"]["count"] >= 3
    with open(prom) as f:
        assert "amgcl_tpu_solve_s" in f.read()


def test_bench_trend_summary_importable(history):
    """trend_summary (what --check attaches to the CI record) works when
    bench.py is loaded by file path — no jax in sight."""
    spec = importlib.util.spec_from_file_location(
        "_bench_t", os.path.join(_REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench._REPO = history
    summ = bench.trend_summary(m)
    assert summ["rollups"]["solve_s"]["count"] >= 3
    assert {r["round"] for r in summ["rows"]} >= {1, 2, 3, 4, 5}
