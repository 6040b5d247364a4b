"""Device busy time, idle share and the longest device operations, from
a JAX profiler trace (``.xplane.pb``) of the measured window.

:func:`load` reads the trace into plain event lists; :func:`reduce`
computes the numbers from them, so it can be checked on a recorded trace
without a chip.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

#: the host annotations of the benchmark's own steps share this prefix
HOST_PREFIX = "chipbench/"
#: the device line whose events are the operations that ran; on a v5e
#: its events are named by their HLO instruction text and nest (a
#: ``while`` spans the operations of its body)
DEVICE_LINES = ("XLA Ops",)
#: stats that carry the JAX scope path of an operation, in order of trust
SCOPE_STATS = ("tf_op", "name", "long_name")


def _stat_map(ev) -> Dict[str, Any]:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def op_name(name: str, stats: Dict[str, Any]) -> str:
    """An operation's name: the JAX scope path where the trace carries
    one, else the HLO instruction name (``fused_down_sweep.14`` out of
    ``%fused_down_sweep.14 = f32[...] custom-call(...)``)."""
    for key in SCOPE_STATS:
        v = stats.get(key)
        if isinstance(v, str) and "/" in v:
            return v
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def load(path: str) -> Dict[str, List[Tuple[float, float, str]]]:
    """``{"device": [(start_ns, end_ns, name), ...] per device plane,
    "host": [(start_ns, end_ns, name)] of the benchmark's annotations}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    host: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name not in DEVICE_LINES:
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    evs.append((s, s + float(ev.duration_ns),
                                op_name(ev.name, _stat_map(ev))))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = float(ev.start_ns)
                        host.append((s, s + float(ev.duration_ns), ev.name))
    return {"device": [v for v in devices.values() if v], "host": host}


def capture(jax, fn):
    """Runs ``fn()`` under the profiler, without the Python tracer:
    (its result, :func:`reduce` of the trace, ``{}`` where the trace has
    no device plane)."""
    import shutil
    import tempfile
    from pathlib import Path
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tdir = tempfile.mkdtemp(prefix="chipbench_trace_")
    try:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            result = fn()
        finally:
            jax.profiler.stop_trace()
        paths = sorted(Path(tdir).rglob("*.xplane.pb"))
        return result, (reduce(load(str(paths[-1]))) if paths else {})
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def _union(intervals: Sequence[Tuple[float, float]]):
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _self_times(events):
    """Each operation's own time: its duration less that of the
    operations nested directly inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [e - s for s, e, _ in events]
    stack: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and not (events[stack[-1]][0] <= s
                             and e <= events[stack[-1]][1]):
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def _label(host, mid: float) -> str:
    """The innermost benchmark annotation open at ``mid``."""
    best = None
    for s, e, name in host:
        if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "outside the benchmark's steps"


def reduce(trace: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """Busy seconds (the union of operation intervals, averaged over the
    device planes), window seconds, the idle share, the ``top``
    operations by their own device time (nested operations' time taken
    out) and idle time by host step. The window is the benchmark's
    ``chipbench/window`` annotation."""
    wins = [(s, e) for s, e, n in trace["host"]
            if n == HOST_PREFIX + "window"]
    if not wins or not trace["device"]:
        return {}
    t0, t1 = wins[0]
    busy = []
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for evs in trace["device"]:
        clipped = [(max(s, t0), min(e, t1), n) for s, e, n in evs
                   if e > t0 and s < t1]
        for (_, _, n), own in zip(clipped, _self_times(clipped)):
            ops[n] = ops.get(n, 0.0) + own * 1e-9
        merged = _union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edge = t0
        for s, e in merged + [[t1, t1]]:
            if s > edge:
                lab = _label(trace["host"], 0.5 * (s + edge))
                idle[lab] = idle.get(lab, 0.0) + (s - edge) * 1e-9
            edge = max(edge, e)
    ndev = len(trace["device"])
    window_s = (t1 - t0) * 1e-9
    busy_s = sum(busy) / ndev
    rank = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "device_ops": [[n, s / ndev] for n, s in rank],
            "idle_gaps": [[n, s / ndev] for n, s in gaps]}
