"""Compile/retrace observer — the silent-latency leg of the telemetry
stack.

On an accelerator the two ways a solve gets slow without any kernel
getting slower are (1) running below the roofline (telemetry/roofline.py)
and (2) recompiling: jit retraces whenever a function sees a new
shape/dtype signature, and a solver loop that perturbs a shape per call
(a growing Krylov basis, a host-side int that should have been static, a
rebuilt operator with a different diagonal count) silently pays seconds
of XLA compile per iteration. Nothing in jax surfaces that per function —
this module does:

* :func:`watched_jit` — drop-in ``jax.jit`` replacement used by our jitted
  entry points. The authoritative registration list is
  :data:`DECLARED_ENTRY_POINTS` below — kept equal to the
  ``watched_jit(name=...)`` call sites in the source by the static
  auditor (analysis/jaxpr_audit.check_entry_points), so this docstring
  can no longer silently drift from reality. It counts **calls** per
  function and
  **traces** per function + abstract-signature (a trace observed for an
  already-seen function with a NEW signature after warmup is recorded
  as a **retrace** event — the "same function, new shape" smell), with
  cache hits = calls − traces.
* a process-global listener on ``jax.monitoring`` duration events
  (``/jax/core/compile/*``) attributes **backend-compile wall time** to
  the watched function currently executing (compiles triggered outside
  any watched function land in the ``<unwatched>`` bucket — probe
  kernels, library internals).
* :func:`snapshot` / :func:`delta` — JSON-clean stats for
  ``SolveReport.compile``, the JSONL sink, and ``bench.py``'s record;
  :func:`findings` turns retrace events into ``telemetry.diagnose()``-
  style findings.

``AMGCL_TPU_COMPILE_WATCH=0`` disables the watcher entirely
(:func:`watched_jit` degrades to plain ``jax.jit``). Kept free of
package-level imports so any ops module can import it without cycles.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Any, Dict, List, Optional

_LOCK = threading.Lock()

#: attribution bucket for compiles observed while no watched function runs
UNWATCHED = "<unwatched>"

#: every watched_jit registration name in the package — the docstring
#: list above, as code. The static auditor
#: (analysis/jaxpr_audit.check_entry_points) asserts this tuple is
#: EXACTLY the set of ``watched_jit(name=...)`` call sites the linter
#: discovers in the source, so the list can no longer drift from
#: reality: adding or renaming a watched entry point without updating
#: it fails `python -m amgcl_tpu.analysis`.
DECLARED_ENTRY_POINTS = (
    "capi.precond_apply",
    "coarsening.device_aggregates",
    "make_solver._solve_fn",
    "ops.dense_window_fused",
    "ops.dense_window_spmv",
    "ops.dia_fused",
    "ops.dia_residual_dot",
    "ops.dia_spmv",
    "ops.dia_spmv_dots",
    "ops.fused_down_sweep",
    "ops.fused_up_sweep",
    "ops.fused_vec",
    "ops.level_setup",
    "ops.segment_galerkin",
    "ops.segment_spgemm",
    "ops.stencil_galerkin",
    "ops.transfer_smooth",
    "ops.well_spmv",
    "parallel.dist_amg_solve",
    "parallel.dist_cg",
    "parallel.dist_cg_pipelined",
    "parallel.dist_exchange",
    "parallel.dist_mis",
    "parallel.dist_stencil_cg",
    "pyamgcl_compat.precond_apply",
    "serve.solve_step",
    "solver.direct.device_inv",
    "telemetry.comm_halo",
    "telemetry.comm_halo_ablated",
    "telemetry.comm_iter",
    "telemetry.comm_iter_ablated",
    "telemetry.comm_psum",
    "telemetry.comm_psum_ablated",
    "telemetry.comm_shard_spmv",
)


def enabled() -> bool:
    return os.environ.get("AMGCL_TPU_COMPILE_WATCH", "1") != "0"


def signature(args, kwargs=None) -> str:
    """Abstract signature of a call: shape/dtype per array leaf (works
    on tracers — this runs at trace time, inside the traced wrapper),
    type:repr for static/python leaves."""
    import numpy as np
    try:
        import jax
        leaves = jax.tree_util.tree_leaves((args, kwargs or {}))
    except Exception:
        leaves = list(args) + list((kwargs or {}).values())
    parts = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            try:
                dt = np.dtype(leaf.dtype).name
            except TypeError:
                dt = str(leaf.dtype)
            parts.append("%s[%s]" % (dt, ",".join(str(d)
                                                  for d in leaf.shape)))
        else:
            parts.append(type(leaf).__name__ + ":" + repr(leaf)[:48])
    return "|".join(parts)


class CompileWatch:
    """Process-global trace/compile counters, keyed by function name and
    abstract signature. All methods are cheap dict updates under a lock —
    nothing here touches the device."""

    def __init__(self):
        self.functions: Dict[str, Dict[str, Any]] = {}
        self.retrace_events: List[Dict[str, Any]] = []
        # per-thread stack of watched fns currently executing — compile
        # durations attribute to the top of the COMPILING thread's stack,
        # so concurrent solves on different threads cannot cross-book
        self._tls = threading.local()
        self._installed = False
        #: process-wide [jaxpr traces, trace seconds, backend compiles,
        #: compile seconds], every function and thread — what a set-up
        #: span reads on entry and exit (telemetry/tracing.py)
        self._counters = [0, 0.0, 0, 0.0]

    @property
    def _stack(self) -> List[str]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    # -- bookkeeping ---------------------------------------------------------

    def _fn(self, name: str) -> Dict[str, Any]:
        rec = self.functions.get(name)
        if rec is None:
            rec = self.functions[name] = {
                "calls": 0, "traces": 0, "backend_compiles": 0,
                "compile_s": 0.0, "trace_sigs": {}, "retraces": 0}
        return rec

    def note_call(self, name: str) -> None:
        with _LOCK:
            self._fn(name)["calls"] += 1

    def note_trace(self, name: str, sig: str) -> None:
        """Called from INSIDE the traced function — fires once per actual
        jit trace (Python side effects run at trace time only)."""
        with _LOCK:
            rec = self._fn(name)
            rec["traces"] += 1
            sigs = rec["trace_sigs"]
            if sig not in sigs and sigs:
                # warmup done (>=1 signature already traced) and a NEW
                # signature arrives: the retrace smell
                rec["retraces"] += 1
                self.retrace_events.append({
                    "fn": name, "sig": sig, "prior_sigs": len(sigs)})
            sigs[sig] = sigs.get(sig, 0) + 1

    # -- jax.monitoring attribution ------------------------------------------

    def install(self) -> "CompileWatch":
        if self._installed:
            return self
        self._installed = True
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                self._on_duration)
        except Exception:
            pass                  # no monitoring API: trace counts only
        return self

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        # '/jax/core/compile/backend_compile_duration' (a compile, or the
        # load of a program found in the persistent cache) and
        # '/jax/core/compile/jaxpr_trace_duration'; everything else on
        # the channel is ignored
        if event.endswith("jaxpr_trace_duration"):
            with _LOCK:
                self._counters[0] += 1
                self._counters[1] += float(duration)
            return
        if "backend_compile" not in event:
            return
        cur = self._stack[-1] if self._stack else UNWATCHED
        with _LOCK:
            rec = self._fn(cur)
            rec["backend_compiles"] += 1
            rec["compile_s"] += float(duration)
            self._counters[2] += 1
            self._counters[3] += float(duration)

    def counters(self):
        """(jaxpr traces, trace seconds, backend compiles, compile
        seconds) observed so far in this process."""
        with _LOCK:
            return tuple(self._counters)

    # -- export --------------------------------------------------------------

    def snapshot(self, fn: Optional[str] = None) -> Dict[str, Any]:
        """JSON-clean stats: one function's record (``fn=``) or the whole
        table + totals. Copies — safe to diff across calls."""
        with _LOCK:
            if fn is not None:
                rec = self.functions.get(fn)
                return _export_fn(rec) if rec else {
                    "calls": 0, "traces": 0, "backend_compiles": 0,
                    "compile_s": 0.0, "signatures": 0, "retraces": 0,
                    "cache_hits": 0}
            out = {"functions": {name: _export_fn(rec)
                                 for name, rec in self.functions.items()},
                   "retrace_events": [dict(e) for e in
                                      self.retrace_events[-50:]]}
            tot = {"calls": 0, "traces": 0, "backend_compiles": 0,
                   "compile_s": 0.0, "retraces": 0}
            for rec in out["functions"].values():
                for k in tot:
                    tot[k] += rec[k]
            tot["compile_s"] = round(tot["compile_s"], 4)
            out["totals"] = tot
            return out

    def reset(self) -> None:
        with _LOCK:
            self.functions.clear()
            self.retrace_events.clear()


def _export_fn(rec: Dict[str, Any]) -> Dict[str, Any]:
    return {"calls": rec["calls"], "traces": rec["traces"],
            "backend_compiles": rec["backend_compiles"],
            "compile_s": round(rec["compile_s"], 4),
            "signatures": len(rec["trace_sigs"]),
            "retraces": rec["retraces"],
            "cache_hits": max(rec["calls"] - rec["traces"], 0)}


_watch: Optional[CompileWatch] = None


def global_watch() -> CompileWatch:
    """The process-global watcher (monitoring listener installed on first
    use)."""
    global _watch
    if _watch is None:
        _watch = CompileWatch()
    return _watch.install()


def snapshot(fn: Optional[str] = None) -> Dict[str, Any]:
    return global_watch().snapshot(fn)


#: package-level alias (``telemetry.compile_snapshot``) — the bare name
#: ``snapshot`` is too generic to re-export
compile_snapshot = snapshot


def delta(before: Dict[str, Any], after: Dict[str, Any]) -> Dict[str, Any]:
    """after − before over one function's snapshot counters (the
    per-solve ``SolveReport.compile`` delta)."""
    out = {}
    for k in ("calls", "traces", "backend_compiles", "retraces",
              "cache_hits"):
        out["new_" + k] = after.get(k, 0) - before.get(k, 0)
    out["new_compile_s"] = round(after.get("compile_s", 0.0)
                                 - before.get("compile_s", 0.0), 4)
    out["new_signatures"] = after.get("signatures", 0) \
        - before.get("signatures", 0)
    return out


def watched_jit(fn=None, name: Optional[str] = None, **jit_kw):
    """``jax.jit`` with observation: counts calls/traces/compile seconds
    per function + signature through the global watch. Usable as a direct
    call (``watched_jit(f, name=..., static_argnames=...)``) or via
    ``functools.partial`` in a decorator position, like ``jax.jit``
    itself. With ``AMGCL_TPU_COMPILE_WATCH=0`` it IS ``jax.jit``."""
    if fn is None:
        return functools.partial(watched_jit, name=name, **jit_kw)
    import jax
    if not enabled():
        return jax.jit(fn, **jit_kw)
    w = global_watch()
    label = name or getattr(fn, "__qualname__",
                            getattr(fn, "__name__", repr(fn)))

    @functools.wraps(fn)
    def traced(*a, **k):
        w.note_trace(label, signature(a, k))
        return fn(*a, **k)

    jitted = jax.jit(traced, **jit_kw)

    @functools.wraps(fn)
    def call(*a, **k):
        # no signature here: flattening the args on EVERY call would tax
        # the solve hot path — the signature is only needed at trace time
        w.note_call(label)
        stack = w._stack
        stack.append(label)
        try:
            return jitted(*a, **k)
        finally:
            stack.pop()

    call._watched_name = label
    call._jitted = jitted
    # forward the jitted-function surface callers rely on (tests clear
    # the cache to force a re-trace; cost analyses lower without calling)
    for attr in ("clear_cache", "lower", "trace", "eval_shape"):
        if hasattr(jitted, attr):
            setattr(call, attr, getattr(jitted, attr))
    return call


def findings(snap: Optional[Dict[str, Any]] = None,
             max_items: int = 5) -> List[Dict[str, Any]]:
    """Retrace events as ``telemetry.diagnose()``-style findings
    ({severity, code, message, suggestion}) — empty when nothing
    retraced."""
    snap = snap if snap is not None else snapshot()
    out = []
    for ev in snap.get("retrace_events", [])[-max_items:]:
        out.append({
            "severity": "warning", "code": "retrace",
            "message": "%s retraced on a new signature after warmup "
                       "(%d prior signature(s)): %s"
                       % (ev["fn"], ev["prior_sigs"], ev["sig"][:120]),
            "suggestion": "if the shape change is unintentional, pad "
                          "inputs to a stable shape or mark the varying "
                          "argument static; every retrace pays a full "
                          "XLA compile"})
    tot = snap.get("totals", {})
    if tot.get("compile_s", 0) > 0 and not out:
        pass                       # compiles without retraces are normal
    return out
