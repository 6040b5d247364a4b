"""The readers of the program's spans (``solve_host_ms``,
``setup_hierarchy_s``, ``setup_first_solve_s``): on a synthetic
recorder, on a program without one, and in a traced run on the CPU."""

import json

import pytest

from conftest import ROOT, write_root

NEW = ("solve_host_ms", "setup_hierarchy_s", "setup_first_solve_s")


@pytest.fixture
def readers():
    from chipbench import spec
    return {name: spec.module(ROOT, "metrics", name) for name in NEW}


@pytest.fixture
def recorder(monkeypatch):
    """A fresh recorder standing in for the program's."""
    from amgcl_tpu.telemetry import tracing
    rec = tracing.SpanRecorder()
    monkeypatch.setattr(tracing, "RECORDER", rec)
    return rec


def solve(rec, sid, start, total, fetch):
    rec.record("solve/prepare", start, start + 0.001, "solve", sid)
    rec.record("solve/fetch", start + 0.002, start + 0.002 + fetch,
               "solve", sid)
    rec.record("solve", start, start + total, None, sid,
               {"first_call": sid == 1, "batched": False})


def test_readers_on_a_synthetic_recorder(readers, recorder):
    recorder.record("setup/hierarchy", 0.0, 30.0, "setup/make_solver", None,
                    {"path": "device"})
    solve(recorder, 1, 40.0, 12.0, 0.5)       # the warm-up
    for k in range(4):
        solve(recorder, 2 + k, 60.0 + k, 0.050 + 0.001 * k, 0.040)
    assert readers["setup_hierarchy_s"].read({}) == pytest.approx(30.0)
    assert readers["setup_first_solve_s"].read({}) == pytest.approx(12.0)
    # the newest `attempted` solves: the warm-up is not among them
    # mean of 50..53 ms less 40 ms of fetch each
    assert readers["solve_host_ms"].read({"attempted": 4}) == \
        pytest.approx(11.5)
    with_warm = readers["solve_host_ms"].read({"attempted": 5})
    assert with_warm == pytest.approx(
        (4 * 11.5 + 1e3 * (12.0 - 0.5)) / 5)
    # fewer solves than the window's: nothing to read
    assert readers["solve_host_ms"].read({"attempted": 6}) is None
    assert readers["solve_host_ms"].read({"attempted": 0}) is None


def test_totals_outlive_the_ring(readers, monkeypatch):
    """The set-up readers read per-name totals, which eviction keeps."""
    from amgcl_tpu.telemetry import tracing
    rec = tracing.SpanRecorder(max_spans=8)
    monkeypatch.setattr(tracing, "RECORDER", rec)
    rec.record("setup/hierarchy", 0.0, 7.5)
    solve(rec, 1, 10.0, 3.0, 1.0)
    for k in range(10):
        solve(rec, 2 + k, 20.0 + k, 0.05, 0.04)
    assert not rec.spans("setup/hierarchy")
    assert readers["setup_hierarchy_s"].read({}) == pytest.approx(7.5)
    assert readers["setup_first_solve_s"].read({}) == pytest.approx(3.0)
    assert readers["solve_host_ms"].read({"attempted": 2}) == \
        pytest.approx(10.0)
    # the ring holds fewer than ten whole solves
    assert readers["solve_host_ms"].read({"attempted": 10}) is None


def test_a_program_without_spans_reads_nothing(readers, monkeypatch):
    from amgcl_tpu.telemetry import tracing
    monkeypatch.delattr(tracing, "RECORDER")
    for name in NEW:
        assert readers[name].read({"attempted": 3}) is None


def test_empty_recorder_reads_nothing(readers, recorder):
    for name in NEW:
        assert readers[name].read({"attempted": 3}) is None


def test_span_metrics_in_a_traced_cpu_run(cpu_run, tmp_path, capsys):
    """A traced run on the CPU reports the three span metrics beside
    the others, from the spans the program recorded in that run."""
    root = write_root(tmp_path, {"poisson_cell": "poisson_small"})
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name, unit, layer, moves in (
            ("solve_host_ms", "ms", "host", "solve_ms"),
            ("setup_hierarchy_s", "s", "setup", "setup_s"),
            ("setup_first_solve_s", "s", "setup", "setup_s")):
        bench["per_layer"].append(
            {"name": name, "unit": unit, "better": "lower",
             "source": "program_span", "layer": layer, "moves": moves,
             "workloads": ["poisson_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    from amgcl_tpu.telemetry import tracing
    tracing.RECORDER.clear()
    rc = cpu_run.main(["--workload", "poisson_cell", "--seed",
                       str(2**31 + 11), "--seconds", "0.5", "--trace", "1"],
                      root=root)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    line = json.loads(out.out.strip().splitlines()[-1])
    m = line["metrics"]
    assert set(m) == {"iters", "setup_compile_s"} | set(NEW)
    assert m["solve_host_ms"]["unit"] == "ms"
    assert 0 < m["solve_host_ms"]["value"]
    setup = [json.loads(s) for s in out.out.splitlines()
             if s.startswith('{"event": "setup"')][0]
    assert 0 < m["setup_hierarchy_s"]["value"] < setup["setup_s"]
    assert 0 < m["setup_first_solve_s"]["value"] < setup["setup_s"]
