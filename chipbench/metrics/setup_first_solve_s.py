"""setup_first_solve_s. Layer: setup (``models/make_solver.py``, the
solve program's first call). Moves: setup_s.

Seconds of the process's first ``solve`` span, from the program's span
recorder (``amgcl_tpu.telemetry.tracing.RECORDER``, read in the process
that ran the cell): the warm-up's trace, lowering, compile or
compile-cache load, and one execution. Nothing to read where the
program has no recorder."""


def read(rec):
    from amgcl_tpu.telemetry import tracing
    recorder = getattr(tracing, "RECORDER", None)
    tot = recorder.totals().get("solve") if recorder else None
    return tot["first_s"] if tot else None
