#!/usr/bin/env python3
"""Readings that set the limit of the comparison that decides ``correct``.

    python3 chipbench/control.py --workload <cell> --seconds <s> \\
        --seeds 1 2 3 ... --control-seeds 1 2 3

On the chip, in one process and at the cell's own size: one set-up of
the cell's entry, then for each seed the window of the cell's traffic for
``--seconds`` with the same sample and check as ``run.py``, giving the
program's readings (the lower reading is their largest); then the
control, the entry's ``control`` (for ``make_solver``: the same
hierarchy and solver with refinement off, float32 throughout), for each
control seed (the upper reading is their smallest). One JSON line per
seed, and a summary line last.
"""

from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, compiles, run, spec  # noqa: E402


def readings(jax, cell, solver, A, seeds, seconds):
    """One JSON-able row per seed: the worst true residual of the
    sampled solutions of the cell's window, and what the window did."""
    tol = cell.entry.tolerance(cell.config)
    rows = []
    for seed in seeds:
        ctx = types.SimpleNamespace(
            jax=jax, entry=cell.entry, solver=solver, traffic=cell.traffic,
            seed=seed, seconds=seconds, tol=tol, rows=A.shape[0],
            trace_seconds=None)
        out = cell.driver.window(ctx, cell.driver.prepare(ctx))
        pairs = [(jax.device_get(b), jax.device_get(x))
                 for b, x in out["sample"]]
        res = check.compare(A, pairs, tol)
        rows.append({"seed": seed, "solves": out["attempted"],
                     "mean_iters": sum(out["iters"]) / len(out["iters"]),
                     "failed": out["failed"],
                     "worst_true_resid": res["worst_true_resid"]["value"],
                     "correct": check.passed(res)})
    return rows


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(root, args.workload)
    import jax
    try:
        run.require_chips(jax, cell.chips)
    except run.NoChip as e:
        print("chipbench: %s" % e, file=sys.stderr)
        return 3
    run.configure_jax(jax, root)
    compiles.CompileLog().install()
    A = cell.build_problem()
    solver = cell.entry.build(cell.config, A)
    program = readings(jax, cell, solver, A, args.seeds, args.seconds)
    for row in program:
        print(json.dumps({"side": "program", **row}), flush=True)
    control_solver = cell.entry.control(cell.config, A, solver)
    control = readings(jax, cell, control_solver, A, args.control_seeds,
                       args.seconds)
    for row in control:
        print(json.dumps({"side": "control", **row}), flush=True)

    def worst(r):  # a non-finite solution reads None: no number at all
        v = r["worst_true_resid"]
        return float("inf") if v is None else v

    print(json.dumps({
        "lower": max(worst(r) for r in program),
        "upper": min(worst(r) for r in control),
        "limit": cell.entry.tolerance(cell.config),
        "control_all_incorrect": not any(r["correct"] for r in control),
        "program_all_correct": all(r["correct"] for r in program)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
