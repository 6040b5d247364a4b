#!/usr/bin/env python3
"""Chip benchmark of amgcl_tpu: time to solution through ``make_solver``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One run, in one process: find the cell's configuration, traffic and the
code they name (``BENCHMARK.json``, ``chipbench/spec.py``), build the
operator with the benchmark's own frozen generator, set up the entry the
configuration names (``chipbench/entries/``; ``make_solver`` for the
cells so far), let the traffic's driver (``chipbench/drivers/``) warm
up every shape it uses and then drive the window for ``--seconds``.
Afterwards the solutions the driver sampled, drawn from the seed, are
checked against the plain reference (``chipbench/check.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from the window's first
``TRACE_SECONDS`` under the profiler (the rest of the window runs
untraced, so every run solves for ``--seconds``). The
last line of standard output is the result; the lines before it are JSON
records of the set-up and of compilations per phase. Without a TPU, or
with fewer chips than the cell asks for, the run exits nonzero and prints
no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, compiles, peaks, spec, work  # noqa


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def require_chips(jax, chips: int):
    """The devices JAX sees; raises NoChip unless they are at least
    ``chips`` TPUs."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip("no TPU: JAX platform is %r" % devices[0].platform)
    if len(devices) < chips:
        raise NoChip("%d chips, the cell asks for %d"
                     % (len(devices), chips))
    return devices


def configure_jax(jax, root: Path):
    """x64 on; the persistent compilation cache at a fixed path inside
    the checkout unless JAX_COMPILATION_CACHE_DIR names one; every
    program cached, however small or fast to compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_enable_x64", True)


#: a traced run profiles the window's first seconds (at least one
#: solve) and solves on untraced to the end of the window; its per-layer
#: metrics read the traced part
TRACE_SECONDS = 5.0


def run_cell(jax, cell: spec.Cell, seed: int, seconds: float, trace: bool,
             log: compiles.CompileLog, emit):
    """Set-up, warm-up, window and check of one cell; the run record the
    metric readers read: the set-up's fields, and whatever the cell's
    driver reports of its window."""
    annotate = jax.profiler.TraceAnnotation
    A = cell.build_problem()
    with annotate("chipbench/entry"):
        solver = cell.entry.build(cell.config, A)
    tol = cell.entry.tolerance(cell.config)
    ctx = types.SimpleNamespace(
        jax=jax, entry=cell.entry, solver=solver, traffic=cell.traffic,
        seed=seed, seconds=seconds, tol=tol, rows=A.shape[0],
        trace_seconds=TRACE_SECONDS if trace else None)
    state = cell.driver.prepare(ctx)
    cell.driver.warm(ctx, state)
    setup_s = time.perf_counter() - T_START
    emit({"event": "setup", "setup_s": setup_s, "rows": A.shape[0],
          "nnz": int(A.nnz), **cell.entry.summary(solver)})
    log.phase = "window"
    out = cell.driver.window(ctx, state)
    log.phase = "after"
    stats = jax.devices()[0].memory_stats() or {}
    sample = out.pop("sample")
    rec = {"setup_s": setup_s, "trace": None, "work": None, **out,
           "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
           "compiles": {p: log.get(p) for p in ("setup", "window")}}
    emit({"event": "compiles", **rec["compiles"]})
    desc = cell.entry.describe(solver) if rec["trace"] else None
    if desc is not None:
        peak = peaks.peaks(jax.devices()[0].device_kind)
        total = {"bytes": 0, "flops": 0, "least_s": 0.0}
        for it in rec["iters"]:
            w = work.least_work(desc, it)
            secs, bound = work.least_seconds(w, peak)
            total["bytes"] += w["bytes"]
            total["flops"] += w["flops"]
            total["least_s"] += secs
        total["bound"] = bound
        rec["work"] = total
        emit({"event": "work", **total, "levels": desc["levels"]})
    pairs = [(jax.device_get(b), jax.device_get(x)) for b, x in sample]
    del sample, ctx, state, solver
    rec["check"] = check.compare(A, pairs, tol)
    return rec


def result_line(cell: spec.Cell, rec, trace: bool, devices):
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.reader.read(rec)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": rec["memory_peak_bytes"]}
    line = {"correct": check.passed(rec["check"]),
            "attempted": rec["attempted"], "failed": rec["failed"],
            "metrics": metrics, "device": dev}
    tr = rec.get("trace")
    if trace and tr:
        dev["busy_s"] = tr["busy_s"]
        dev["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["check"] = rec["check"]
    return line


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: Path = ROOT) -> int:
    args = parse_args(argv)
    cell = spec.load_cell(root, args.workload)
    import jax
    try:
        devices = require_chips(jax, cell.chips)
    except NoChip as e:
        print("chipbench: %s" % e, file=sys.stderr)
        return 3
    configure_jax(jax, root)
    import amgcl_tpu  # noqa: F401  the system under test, before any work
    log = compiles.CompileLog().install()

    def emit(obj):
        print(json.dumps(obj, default=str), flush=True)

    rec = run_cell(jax, cell, args.seed, args.seconds, bool(args.trace),
                   log, emit)
    line = result_line(cell, rec, bool(args.trace), devices)
    for name, c in line["check"].items():
        print("check %s %r limit %r" % (name, c["value"], c["limit"]),
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
