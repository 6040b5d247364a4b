"""CPU tests of the benchmark harness: ``python -m pytest chipbench/tests``.

They run on the CPU at small sizes; nothing here measures a chip."""

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

#: a cell small enough for the CPU: Poisson 12^3 and a 2,000-row FE
#: operator, same solvers, refinement and traffic shape as the chip cells
SMALL_CONFIGS = {
    "poisson_small": {
        "generator": "poisson3d", "entry": "make_solver", "n": 12,
        "precond": {"coarsening": {"type": "smoothed_aggregation"},
                    "relax": {"type": "spai0"}, "dtype": "float32",
                    "coarse_enough": 200},
        "solver": {"type": "cg", "tol": 1e-8, "maxiter": 100},
        "refine": 3, "control": {"refine": 0}},
    "fe_small": {
        "generator": "fe_like", "entry": "make_solver", "rows": 2000,
        "nnz_target": 40000,
        "matrix_seed": 0,
        "precond": {"coarsening": {"type": "smoothed_aggregation"},
                    "relax": {"type": "spai0"}, "dtype": "float32",
                    "coarse_enough": 200},
        "solver": {"type": "bicgstab", "tol": 1e-8, "maxiter": 300},
        "refine": 2, "control": {"refine": 0}},
}


def write_root(tmp: Path, cells, traffic=None):
    """A checkout-shaped directory with a BENCHMARK.json holding ``cells``
    ({cell: config name}) over SMALL_CONFIGS, and their files."""
    traffic = traffic or {"driver": "closed_loop", "callers": 1,
                          "rhs": "normal", "check_sample": 3}
    (tmp / "chipbench" / "traffic").mkdir(parents=True)
    (tmp / "chipbench" / "configs").mkdir(parents=True)
    (tmp / "chipbench" / "traffic" / "small.json").write_text(
        json.dumps(traffic))
    configs = []
    for name in sorted(set(cells.values())):
        f = "chipbench/configs/%s.json" % name
        (tmp / f).write_text(json.dumps(SMALL_CONFIGS[name]))
        configs.append({"name": name, "source": "test", "file": f,
                        "reduced": [], "why": "test"})
    bench = {
        "command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
        "run_seconds": 1, "configs": configs,
        "workloads": [{"name": c, "config": cfg, "traffic": "small",
                       "chips": 1, "why": "test"}
                      for c, cfg in cells.items()],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "solve_ms", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock",
             "workloads": sorted(cells)}],
        "per_layer": [
            {"name": "iters", "unit": "iters", "better": "lower",
             "source": "program_counter", "layer": "solver",
             "moves": "solve_ms", "workloads": sorted(cells)},
            {"name": "setup_compile_s", "unit": "s", "better": "lower",
             "source": "program_span", "layer": "setup",
             "moves": "setup_s"}]}
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture
def make_solver_entry():
    """The ``make_solver`` entry module the cells load, to patch."""
    from chipbench import spec
    return spec.module(ROOT, "entries", "make_solver")


@pytest.fixture
def cpu_run(monkeypatch):
    """``run`` with the look for a chip skipped: everything else as on
    the chip."""
    from chipbench import run
    monkeypatch.setattr(run, "require_chips",
                        lambda jax, chips: jax.devices())
    return run
