"""JSONL metrics sink — one JSON object per line, shared by bench.py,
cli.py, make_solver and the distributed solvers.

Schema convention (shared with BENCH_*.json): flat JSON
objects; every stamped record carries ``ts`` (unix seconds) and ``ts_iso``;
solver-originated records carry an ``event`` field ("solve", "setup",
"profile", "bench", "tier1_check", "health", "doctor", ...) plus the
:class:`SolveReport` fields (iters, resid, convergence_rate,
wall_time_s, solver, history, hierarchy, health).

The process-global default sink is a no-op until configured — either
programmatically (``set_default_sink(JsonlSink(path))``) or by exporting
``AMGCL_TPU_TELEMETRY=/path/to/out.jsonl`` — so library code can call
:func:`emit` unconditionally.

IMPORTANT: this module is stdlib-only AND free of package-relative imports
on purpose: bench.py's supervisor (which must never import jax) loads it
directly by file path with importlib, bypassing ``amgcl_tpu/__init__``.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import threading
import time
from typing import Any, Dict, Optional


def _jsonable(obj):
    if hasattr(obj, "tolist"):
        return obj.tolist()
    if hasattr(obj, "item"):
        return obj.item()
    return str(obj)


def _clean(obj):
    """Replace non-finite floats with their string names ("nan"/"inf") so
    every emitted line is strict RFC JSON — json.dumps would otherwise
    write bare NaN/Infinity tokens, making exactly the records that
    describe solver breakdowns unparseable to jq/JSON.parse consumers.
    The string keeps the breakdown signal a null would erase."""
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else str(obj)
    if isinstance(obj, dict):
        return {k: _clean(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_clean(v) for v in obj]
    if hasattr(obj, "tolist"):
        return _clean(obj.tolist())
    if hasattr(obj, "item"):
        return _clean(obj.item())
    return obj


def stamp(record: Dict[str, Any], commit: Optional[str] = None,
          now: Optional[float] = None) -> Dict[str, Any]:
    """Copy of ``record`` with ``ts``/``ts_iso`` (and optionally
    ``commit``) appended — setdefault semantics, existing stamps win.
    Field order matches the historical bench.py last-good records so the
    on-disk artifact stays byte-compatible."""
    rec = dict(record)
    rec.setdefault("ts", time.time() if now is None else now)
    # ts_iso always renders the record's ts — a pre-stamped ts must not
    # disagree with it
    rec.setdefault("ts_iso", time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime(rec["ts"])))
    if commit is not None:
        rec.setdefault("commit", commit)
    return rec


def git_commit(repo: str) -> Optional[str]:
    """Short HEAD hash of ``repo``, or None (never raises)."""
    try:
        return subprocess.run(
            ["git", "-C", repo, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10).stdout.strip() \
            or None
    except Exception:
        return None


def write_json_atomic(path: str, record: Dict[str, Any]) -> None:
    """Single-object JSON file via tmp + rename (a reader never sees a
    torn file). No non-finite cleaning —
    this path reproduces the historical bench artifact byte-for-byte."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f, default=_jsonable)
    os.replace(tmp, path)


def max_sink_bytes() -> int:
    """Size cap for file sinks from ``AMGCL_TPU_TELEMETRY_MAX_BYTES``
    (0 / unset / unparseable = unbounded, the historical behavior)."""
    try:
        return int(os.environ.get("AMGCL_TPU_TELEMETRY_MAX_BYTES", "0"))
    except ValueError:
        return 0


class JsonlSink:
    """Append-mode JSONL writer. ``path`` XOR ``stream``; file sinks
    open/write/close per record so concurrent emitters (processes or
    threads) interleave at line granularity and a crash never loses
    buffered lines.

    ``clean_records=False`` opts out of the non-finite-float cleaning for
    surfaces with a pre-existing schema contract (bench.py's stdout line,
    whose consumers round-trip bare NaN tokens via Python json).

    File sinks rotate: once the file exceeds ``max_bytes`` (default from
    ``AMGCL_TPU_TELEMETRY_MAX_BYTES``; 0 = unbounded) the next emit
    renames ``out.jsonl`` -> ``out.jsonl.1`` (replacing any previous
    ``.1``) and starts fresh — a long-running service holds at most
    ~2x the cap on disk instead of growing without bound. Rotation is
    checked before the write, so a single record never splits across
    the two files.

    The write path is serialized by a per-instance lock: the serve
    worker thread and foreground callers share the process-global sink,
    and an unlocked rotate-then-append pair can interleave — thread A
    rotates, thread B (who sized the file before the rename) rotates
    again, and A's freshly written records vanish into a replaced
    ``.1``. The lock makes size-check + rename + append one atomic
    step; stream writes take it too so two threads' lines cannot
    interleave mid-record on buffered streams."""

    def __init__(self, path: Optional[str] = None, stream=None,
                 stamp_records: bool = True, clean_records: bool = True,
                 max_bytes: Optional[int] = None):
        if (path is None) == (stream is None):
            raise ValueError("JsonlSink needs exactly one of path/stream")
        self.path = path
        self.stream = stream
        self.stamp_records = stamp_records
        self.clean_records = clean_records
        self.max_bytes = max_sink_bytes() if max_bytes is None \
            else int(max_bytes)
        self._lock = threading.Lock()

    def _maybe_rotate(self):
        if not self.max_bytes or self.max_bytes <= 0:
            return
        try:
            if os.path.getsize(self.path) >= self.max_bytes:
                os.replace(self.path, self.path + ".1")
        except OSError:
            pass          # missing file (first write) or a racing rotator

    def emit(self, record: Optional[Dict[str, Any]] = None,
             **fields) -> Dict[str, Any]:
        rec = dict(record or {})
        rec.update(fields)
        if self.stamp_records:
            rec = stamp(rec)
        line = json.dumps(_clean(rec) if self.clean_records else rec,
                          default=_jsonable)
        with self._lock:
            if self.stream is not None:
                self.stream.write(line + "\n")
                self.stream.flush()
            else:
                self._maybe_rotate()
                with open(self.path, "a") as f:
                    f.write(line + "\n")
        return rec

    def close(self):
        pass  # nothing held open

    def __repr__(self):
        return "JsonlSink(%r)" % (self.path or getattr(
            self.stream, "name", self.stream))


class NullSink:
    """Default sink: validates nothing, writes nothing."""

    def emit(self, record: Optional[Dict[str, Any]] = None,
             **fields) -> Dict[str, Any]:
        rec = dict(record or {})
        rec.update(fields)
        return rec

    def close(self):
        pass


_default_sink = None
_default_sink_explicit = False   # a set_default_sink(NullSink()) must
#                                  stick — only env-derived NullSinks are
#                                  re-resolved against the env var


def get_default_sink():
    """The process-global sink, from ``AMGCL_TPU_TELEMETRY`` (a JSONL
    path) when set, else a NullSink. The env var is re-checked while the
    default is still an env-derived NullSink, so exporting it after the
    first solve still takes effect — but an explicit set_default_sink
    (including an explicit NullSink opt-out) always wins."""
    global _default_sink
    if not _default_sink_explicit and (
            _default_sink is None or isinstance(_default_sink, NullSink)):
        path = os.environ.get("AMGCL_TPU_TELEMETRY")
        if path:
            _default_sink = JsonlSink(path)
        elif _default_sink is None:
            _default_sink = NullSink()
    return _default_sink


def set_default_sink(sink) -> None:
    """Install ``sink`` (None resets to the env-driven default)."""
    global _default_sink, _default_sink_explicit
    _default_sink = sink
    _default_sink_explicit = sink is not None


_emit_warned = False


def emit(record: Optional[Dict[str, Any]] = None, **fields) -> Dict[str, Any]:
    """Emit through the process-global default sink. Never raises:
    telemetry must not turn a converged solve into a failure (a typo'd
    AMGCL_TPU_TELEMETRY path, a read-only mount, a full disk). A failing
    sink warns on the first drop and stays quiet after."""
    global _emit_warned
    try:
        return get_default_sink().emit(record, **fields)
    except Exception as e:
        if not _emit_warned:
            _emit_warned = True
            import warnings
            warnings.warn("telemetry sink emit failed (%r) — records "
                          "will be dropped" % (e,))
        rec = dict(record or {})
        rec.update(fields)
        return rec
