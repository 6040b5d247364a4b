"""solve_host_ms. Layer: host (the ``make_solver`` call path on the
host, ``models/make_solver.py``). Moves: solve_ms.

Host milliseconds per solve in which the program is not waiting on the
device: over the window's solves, the mean of each ``solve`` span less
its ``solve/fetch`` child, from the program's span recorder
(``amgcl_tpu.telemetry.tracing.RECORDER``, read in the process that ran
the cell). The window's solves are the newest ``attempted`` ``solve``
spans; nothing to read where the program holds fewer, or has no
recorder."""


def read(rec):
    from amgcl_tpu.telemetry import tracing
    recorder = getattr(tracing, "RECORDER", None)
    n = int(rec.get("attempted") or 0)
    if recorder is None or n <= 0:
        return None
    ring = recorder.spans()
    solves = [s for s in ring if s[0] == "solve"][-n:]
    fetch = {s[4]: s[2] - s[1] for s in ring if s[0] == "solve/fetch"}
    if len(solves) < n or any(s[4] not in fetch for s in solves):
        return None
    return 1e3 * sum(s[2] - s[1] - fetch[s[4]] for s in solves) / n
