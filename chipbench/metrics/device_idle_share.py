"""device_idle_share. Layer: device. Moves: solve_ms.

The share of the traced window in which no operation ran on the device,
in percent: 1 minus the union of the device operations' intervals over
the window, from the profiler trace (``chipbench/devtrace.py``)."""


def read(rec):
    tr = rec.get("trace") or {}
    if tr.get("idle_share") is None:
        return None
    return 100.0 * tr["idle_share"]
