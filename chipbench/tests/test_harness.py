"""A whole run on the CPU, with the look for a chip skipped: cells found
by name, the result line, and the comparison that decides ``correct``
coming out false for the control and for a broken timed path."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT, write_root


def last_json(text):
    return json.loads(text.strip().splitlines()[-1])


def run_cell(run, root, cell, capsys, seed=2**31 + 5, trace=0):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "0.5", "--trace", str(trace)], root=root)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return last_json(out.out), out


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    return write_root(tmp_path_factory.mktemp("bench"),
                      {"poisson_cell": "poisson_small",
                       "fe_cell": "fe_small"})


@pytest.mark.parametrize("cell", ["poisson_cell", "fe_cell"])
def test_new_cell_runs_from_files_alone(cpu_run, small_root, cell, capsys):
    """A cell that exists only as files (BENCHMARK.json entry, config,
    traffic) runs with no code edited, and its line has every key."""
    line, out = run_cell(cpu_run, small_root, cell, capsys)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "check"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"setup_s", "solve_ms"}
    assert line["device"]["platform"] == "cpu"
    w = line["check"]["worst_true_resid"]
    assert w["value"] <= w["limit"] == 1e-8
    assert "check worst_true_resid" in out.err.strip().splitlines()[-2]
    compiles = [json.loads(s) for s in out.out.splitlines()
                if s.startswith('{"event": "compiles"')][0]
    assert compiles["window"]["backend_compiles"] == 0


def cases_of(seed, traffic, count=6, rows=40):
    import jax
    import numpy as np
    from chipbench import rhs
    cs = rhs.Cases(jax, traffic, seed, rows)
    return [cs.case(i) for i in range(count)], \
        [np.asarray(cs.make(cs.case(i))) for i in range(count)], \
        np.asarray(cs.warm())


def test_same_seed_same_inputs():
    traffic = {"rhs": "normal"}
    ia, a, wa = cases_of(2**31 + 9, traffic)
    ib, b, wb = cases_of(2**31 + 9, traffic)
    _, c, _ = cases_of(2**31 + 10, traffic)
    assert ia == ib == list(range(6)) and (wa == wb).all()
    for x, y, z in zip(a, b, c):
        assert (x == y).all() and not (x == z).all()
    # no case repeats in a run, and the warm-up vector is none of them
    flat = {tuple(v[:4]) for v in a + [wa]}
    assert len(flat) == 7


def test_per_layer_metrics_in_a_traced_run(cpu_run, small_root, capsys):
    line, _ = run_cell(cpu_run, small_root, "poisson_cell", capsys,
                       trace=1)
    # the CPU has no device plane: nothing is read from a device trace
    assert set(line["metrics"]) == {"iters", "setup_compile_s"}
    assert "busy_s" not in line["device"]


def test_control_comes_out_incorrect(cpu_run, small_root, capsys,
                                     monkeypatch, make_solver_entry):
    """The configuration's control (refinement off: float32 throughout)
    fails the comparison."""
    build = make_solver_entry.build

    def control(config, A, precond=None, refine=None):
        return build(config, A, precond,
                     refine=int(config.get("control", {}).get("refine", 0)))

    monkeypatch.setattr(make_solver_entry, "build", control)
    for cell in ("poisson_cell", "fe_cell"):
        line, _ = run_cell(cpu_run, small_root, cell, capsys)
        assert line["correct"] is False, line["check"]


class Broken:
    """The bundle the window drives, with its answers broken."""

    def __init__(self, inner, fault):
        self._inner, self._fault = inner, fault

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __call__(self, b):
        x, info = self._inner(b)
        return self._fault(x), info


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_broken_timed_path_comes_out_incorrect(cpu_run, small_root, capsys,
                                               monkeypatch, fault,
                                               make_solver_entry):
    """A solve that hands back its starting state, and an answer altered
    where it is produced, each make ``correct`` false."""
    import jax.numpy as jnp
    faults = {"unchanged": lambda x: jnp.zeros_like(x),
              "altered": lambda x: x.at[x.shape[0] // 2].add(1e-3)}
    build = make_solver_entry.build
    monkeypatch.setattr(
        make_solver_entry, "build",
        lambda *a, **k: Broken(build(*a, **k), faults[fault]))
    line, _ = run_cell(cpu_run, small_root, "poisson_cell", capsys)
    assert line["correct"] is False
    assert line["check"]["worst_true_resid"]["value"] > 1e-8


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "poisson128_solve", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_bare_checkout_exits_nonzero_without_a_result(tmp_path):
    """Only BENCHMARK.json and the benchmark's own files: no program to
    run, so no result, even where a chip is found."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, '.'); from chipbench import run; "
            "run.require_chips = lambda jax, chips: jax.devices(); "
            "sys.exit(run.main(['--workload', 'poisson128_solve', "
            "'--seed', '1', '--seconds', '1']))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       env=dict(env, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "amgcl_tpu" in p.stderr
    assert '"correct"' not in p.stdout


def test_unknown_cell_and_device_are_errors(tmp_path):
    from chipbench import peaks, spec
    with pytest.raises(spec.SpecError):
        spec.load_cell(ROOT, "no_such_cell")
    with pytest.raises(spec.SpecError):
        spec.load_cell(ROOT, "../etc")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_benchmark_file_names_what_exists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / (m["name"] + ".py")).is_file()
    from chipbench import spec
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        for kind, name in (("problems", cell.config["generator"]),
                           ("entries", cell.config["entry"]),
                           ("drivers", cell.traffic["driver"])):
            assert (ROOT / "chipbench" / kind / (name + ".py")).is_file()


def test_non_finite_solution_fails_the_check():
    import numpy as np
    import scipy.sparse as sp
    from chipbench import check
    A = sp.identity(3, format="csr")
    b = np.ones(3)
    good = check.compare(A, [(b, b)], 1e-8)
    assert check.passed(good) and good["worst_true_resid"]["value"] == 0.0
    bad = check.compare(A, [(b, b), (b, np.array([1.0, np.nan, 1.0]))], 1e-8)
    assert bad["worst_true_resid"]["value"] is None
    assert not check.passed(bad)
    assert not check.passed(check.compare(A, [], 1e-8))
    json.dumps(bad, allow_nan=False)


@pytest.mark.parametrize("cell", ["poisson_cell", "fe_cell"])
def test_control_readings(cpu_run, small_root, capsys, cell):
    """``control.py``, which reads the limit's two readings on the chip,
    finds the program correct and its control not, at a small size."""
    from chipbench import control
    rc = control.main(["--workload", cell, "--seconds", "0.3", "--seeds",
                       "1", str(2**31 + 3), "--control-seeds", "4", "5"],
                      root=small_root)
    assert rc == 0
    summary = last_json(capsys.readouterr().out)
    assert summary["program_all_correct"] and summary["control_all_incorrect"]
    assert summary["lower"] <= summary["limit"] < summary["upper"]


def test_fixed_cases_are_one_set_in_seeded_order():
    """A traffic with ``case_seed`` gives every run the same cases; the
    run's seed orders the first ``ordered_cases`` of them, and the cases
    after them are fresh, so none repeats however many solves a window
    completes."""
    traffic = {"rhs": "normal", "case_seed": 0, "ordered_cases": 3}
    runs = [cases_of(s, traffic) for s in (2**31 + 1, 2**31 + 2, 2**31 + 3)]
    for idx, vecs, _ in runs:
        assert sorted(idx[:3]) == [0, 1, 2] and idx[3:] == [3, 4, 5]
        assert len({tuple(v[:4]) for v in vecs}) == 6
    key = lambda r: sorted(tuple(v[:3]) for v in r[1])  # noqa: E731
    assert key(runs[0]) == key(runs[1]) == key(runs[2])
    assert len({tuple(r[0][:3]) for r in runs}) > 1


#: a kind of traffic, an entry and an end-to-end metric that the
#: benchmark does not have, each a file of its own
NEW_DRIVER = """
import time
from chipbench import rhs


def prepare(ctx):
    return rhs.Cases(ctx.jax, ctx.traffic, ctx.seed, ctx.rows)


def warm(ctx, cases):
    ctx.solver(cases.warm())[0].block_until_ready()


def window(ctx, cases):
    t0, pairs, failed = time.perf_counter(), [], 0
    for i in range(int(ctx.traffic["count"])):
        b = cases.make(cases.case(i))
        x, info = ctx.solver(b)
        x.block_until_ready()
        failed += not ctx.entry.report(info, ctx.tol)[1]
        pairs.append((b, x))
    return {"batch_s": time.perf_counter() - t0, "attempted": len(pairs),
            "failed": failed, "iters": [], "sample": pairs}
"""
NEW_ENTRY = """
def build(config, A):
    import jax
    import jax.numpy as jnp
    M = jnp.asarray(A.toarray())
    f = jax.jit(lambda b: jnp.linalg.solve(M, b.astype(M.dtype)))
    return lambda b: (f(b), None)


def tolerance(config):
    return float(config["tol"])


def report(info, tol):
    return 0, True


def summary(solver):
    return {}


def describe(solver):
    return None
"""
NEW_METRIC = """
def read(rec):
    return rec["attempted"] / rec["batch_s"]
"""


def test_new_traffic_kind_runs_from_files_alone(cpu_run, tmp_path, capsys):
    """A cell with a traffic kind, an entry and a metric that no existing
    file knows runs from added files alone: its driver, entry and metric
    under the checkout's ``chipbench/``, its configuration, traffic and
    ``BENCHMARK.json`` entry."""
    root = write_root(tmp_path, {"poisson_cell": "poisson_small"})
    for kind, name, text in (("drivers", "fixed_count", NEW_DRIVER),
                             ("entries", "dense_direct", NEW_ENTRY),
                             ("metrics", "rhs_per_s", NEW_METRIC)):
        (root / "chipbench" / kind).mkdir(parents=True, exist_ok=True)
        (root / "chipbench" / kind / (name + ".py")).write_text(text)
    (root / "chipbench" / "traffic" / "count4.json").write_text(json.dumps(
        {"driver": "fixed_count", "rhs": "normal", "count": 4}))
    (root / "chipbench" / "configs" / "dense.json").write_text(json.dumps(
        {"generator": "poisson3d", "entry": "dense_direct", "n": 6,
         "tol": 1e-10}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dense", "source": "test",
                            "file": "chipbench/configs/dense.json",
                            "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dense_cell", "config": "dense",
                               "traffic": "count4", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "rhs_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["dense_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    line, _ = run_cell(cpu_run, root, "dense_cell", capsys)
    assert line["correct"] is True and line["attempted"] == 4
    assert set(line["metrics"]) == {"setup_s", "rhs_per_s"}
    assert line["check"]["checked"]["value"] == 4
    # the cells already there still run the code beside the harness
    line, _ = run_cell(cpu_run, root, "poisson_cell", capsys)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "solve_ms"}
