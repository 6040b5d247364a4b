"""Tracing: named scopes for device code, and one span recorder for the
host side of set-up, solve and serve.

``phase(name)`` wraps traced code in ``jax.named_scope`` so the compiled
ops carry an ``amgcl/...`` scope path: a ``jax.profiler.trace()`` capture of
one V-cycle then groups device time under pre_smooth / restrict /
coarse_solve / prolong / post_smooth exactly like the reference's tic/toc
tree (amgcl/profiler.hpp). Zero runtime cost — scopes only annotate op
metadata at trace time.

``annotate(name)`` is the bare host-side sibling (``jax.profiler
.TraceAnnotation``).

``span(name, **attrs)`` is the host span: a context manager that records
``(name, start, end, parent, solve_id, attrs)`` in ``time.perf_counter()``
seconds into :data:`RECORDER`, a bounded thread-safe ring that keeps the
newest spans, plus per-name totals (count, seconds, the first span's
seconds) that eviction never loses. While a ``jax.profiler`` trace is
being taken the span also opens ``TraceAnnotation("amgcl/" + name)``, so
it sits on the device trace's clock; with no trace active it costs two
clock reads and one append (spans that close inside an open span on the
same thread reach the ring together, under one lock, when the outermost
closes). The span names:

* ``solve`` (one per ``make_solver`` call, a per-process ``solve_id``
  that its children carry; attributes ``first_call``, ``batched``) with
  the consecutive steps ``solve/prepare``, ``solve/dispatch``,
  ``solve/fetch`` and ``solve/report``; the report's telemetry legs are
  ``solve/report/<leg>``;
* ``setup/make_solver``, ``setup/hierarchy`` (attribute ``path``:
  device, host or hybrid), ``setup/level<i>/<stage>``,
  ``setup/coarse_solver``, ``setup/reorder``, ``setup/system_operator``,
  ``setup/df32_selfcheck``. Every ``setup/`` span carries the JAX traces,
  trace seconds, backend compiles and compile seconds (a compile-cache
  load counts as a compile) accrued while it was open
  (``compile_watch``'s listener);
* ``serve/<phase>`` from :class:`RequestSpans`.

:class:`RequestSpans` is the serving-path recorder: per-request phase
spans (queue wait, padding, compile, device solve, sync) of one service,
exported as a Chrome/Perfetto track compatible with
``utils.profiler.Profiler.to_chrome_trace``'s epoch-merge — pass the same
``epoch`` and the request track lands on the CLI profiler's timeline
(``cli.py --serve --trace``). It is a :class:`SpanRecorder` of its own
and also records into :data:`RECORDER`.
"""

from __future__ import annotations

import itertools
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, List, Optional, Sequence, Tuple

from amgcl_tpu.analysis import lockwitness as _lockwitness

PREFIX = "amgcl/"


def phase(name: str):
    """Trace-time named scope ``amgcl/<name>`` for device code."""
    try:
        import jax
        return jax.named_scope(PREFIX + name)
    except Exception:
        return nullcontext()


def annotate(name: str):
    """Host-side profiler annotation ``amgcl/<name>`` for un-traced work
    (shows as a span on the host timeline of a ``jax.profiler`` trace)."""
    try:
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(PREFIX + name)
    except Exception:
        return nullcontext()


#: one recorded span: (name, start_s, end_s, parent name or None,
#: solve_id or None, attrs dict or None)
SpanRecord = Tuple[str, float, float, Optional[str], Optional[int],
             Optional[Dict[str, Any]]]


def _fold(totals: Dict[str, List[float]],
          entries: Sequence[SpanRecord]) -> None:
    """Add ``entries`` (oldest first) to ``{name: [count, seconds, first
    span's seconds]}``."""
    get = totals.get
    for name, start, end, _, _, _ in entries:
        tot = get(name)
        if tot is None:
            totals[name] = [1, end - start, end - start]
        else:
            tot[0] += 1
            tot[1] += end - start


class SpanRecorder:
    """Bounded thread-safe ring of spans (the newest ``max_spans`` are
    kept) with per-name totals that eviction never loses.

    A record is one locked list extend: the per-name totals of the spans
    still in the ring are summed when :meth:`totals` is read, and spans
    leave the ring in batches of ``max_spans // 16``, their totals folded
    in as they go."""

    #: Chrome-trace category of the exported events
    CATEGORY = "amgcl"

    def __init__(self, max_spans: int = 65536):
        self.max_spans = int(max_spans)
        self._cap = self.max_spans + max(1, self.max_spans // 16)
        self._lock = threading.Lock()
        # runtime lock witness seam (identity when the knob is off)
        _lockwitness.maybe_instrument(self, "tracing")
        self._ring: List[SpanRecord] = []
        #: the totals of the spans that left the ring
        self._gone: Dict[str, List[float]] = {}
        self._n_gone = 0
        self._t0 = time.perf_counter()

    def record(self, name: str, start: float, end: float,
               parent: Optional[str] = None,
               solve_id: Optional[int] = None,
               attrs: Optional[Dict[str, Any]] = None) -> None:
        self.record_many(((name, start, end, parent, solve_id, attrs),))

    def record_many(self, entries: Sequence[SpanRecord]) -> None:
        with self._lock:
            ring = self._ring
            ring.extend(entries)
            if len(ring) > self._cap:
                over = len(ring) - self.max_spans
                _fold(self._gone, ring[:over])
                del ring[:over]
                self._n_gone += over

    def _snapshot(self) -> Tuple[List[SpanRecord], int]:
        """The newest ``max_spans`` spans and how many were evicted."""
        with self._lock:
            ring = self._ring
            extra = max(0, len(ring) - self.max_spans)
            return ring[extra:], self._n_gone + extra

    def spans(self, name: Optional[str] = None) -> List[SpanRecord]:
        """The ring, oldest first (a span is appended when it ends, so
        children precede their parent); ``name`` filters."""
        ring, _ = self._snapshot()
        if name is None:
            return ring
        return [s for s in ring if s[0] == name]

    def totals(self) -> Dict[str, Dict[str, float]]:
        """``{name: {"count", "total_s", "first_s"}}`` over every span
        ever recorded, evicted ones included."""
        with self._lock:
            totals = {name: list(tot) for name, tot in self._gone.items()}
            ring = list(self._ring)
        # evicted spans are the older: a name's first span stays theirs
        _fold(totals, ring)
        return {name: {"count": int(c), "total_s": t, "first_s": f}
                for name, (c, t, f) in totals.items()}

    @property
    def evicted(self) -> int:
        return self._snapshot()[1]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._gone.clear()
            self._n_gone = 0

    def _path(self, entry: SpanRecord) -> str:
        return entry[0]

    def to_chrome_trace(self, tid: int = 0,
                        tid_name: Optional[str] = None, pid: int = 0,
                        epoch: Optional[float] = None) -> Dict:
        """Chrome/Perfetto trace-event dict of the spans in the ring, one
        complete event each — concatenate ``traceEvents`` with other
        tracks sharing the same ``epoch`` (a ``time.perf_counter()``
        reference, see ``Profiler.to_chrome_trace``)."""
        t0 = self._t0 if epoch is None else epoch
        ring, evicted = self._snapshot()
        events = []
        if tid_name:
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid, "args": {"name": tid_name}})
        for entry in ring:
            _, start, end, _, solve_id, attrs = entry
            path = self._path(entry)
            args: Dict[str, Any] = {"path": path}
            if solve_id is not None:
                args["solve_id"] = solve_id
            if attrs:
                args.update(attrs)
            events.append({
                "name": path.rsplit("/", 1)[-1], "cat": self.CATEGORY,
                "ph": "X", "ts": round((start - t0) * 1e6, 3),
                "dur": round((end - start) * 1e6, 3),
                "pid": pid, "tid": tid, "args": args})
        if evicted:
            last_end = max((e[2] for e in ring), default=t0)
            events.append({
                "name": "spans_dropped", "cat": self.CATEGORY,
                "ph": "i", "s": "g",
                "ts": round((last_end - t0) * 1e6, 3),
                "pid": pid, "tid": tid,
                "args": {"dropped": evicted, "cap": self.max_spans}})
        return {"traceEvents": events, "displayTimeUnit": "ms"}


#: the process-global recorder every :func:`span` records into
RECORDER = SpanRecorder()

_SOLVE_IDS = itertools.count(1)
_perf = time.perf_counter
#: spans that closed inside a still-open span on this thread wait here
#: and reach the ring in one locked append when the outermost span
#: closes (or this many are waiting)
_FLUSH_AT = 256


class _Frame:
    """One thread's open spans, solve id and spans waiting for the
    ring."""

    __slots__ = ("stack", "solve_id", "buf")

    def __init__(self):
        self.stack: List["Span"] = []
        self.solve_id: Optional[int] = None
        self.buf: List[SpanRecord] = []


class _ThreadState(threading.local):
    def __init__(self):
        self.frame = _Frame()


_state = _ThreadState()

try:
    from jax.profiler import TraceAnnotation as _TraceAnnotation
    #: True while a ``jax.profiler`` trace is being taken
    _profiling = _TraceAnnotation.is_enabled
except Exception:                   # no profiler: spans record only
    _TraceAnnotation = None

    def _profiling() -> bool:
        return False


class Span:
    """The context manager :func:`span` returns: ``set(**attrs)`` adds
    attributes while it is open."""

    __slots__ = ("name", "attrs", "_t0", "_ann", "_st")

    def __init__(self, name: str,
                 attrs: Optional[Dict[str, Any]] = None):
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> None:
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    def __enter__(self):
        self._st = st = _state.frame
        st.stack.append(self)
        self._ann = None
        if _profiling():
            self._ann = _TraceAnnotation(PREFIX + self.name)
            self._ann.__enter__()
        self._t0 = _perf()
        return self

    def __exit__(self, et, ev, tb):
        self._finish(_perf())
        return False

    def _finish(self, t1: float) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        st = self._st
        stack = st.stack
        stack.pop()
        buf = st.buf
        buf.append((self.name, self._t0, t1,
                    stack[-1].name if stack else None, st.solve_id,
                    self.attrs))
        if not stack or len(buf) >= _FLUSH_AT:
            st.buf = []
            RECORDER.record_many(buf)


class _SolveScope(Span):
    """The ``solve`` span: takes the next per-process solve id, which
    every span opened inside it carries. Its children cost no ``with``
    apiece:

    * ``step(name)`` ends the step opened last and opens the next, one
      clock read for both; the last step ends with the span;
    * ``begin(name)`` opens a leg, a child of the open leg, else of the
      open step, else of the span; ``end()`` closes the leg opened last.
      Legs still open when the span ends (an exception left them) end
      with it."""

    __slots__ = ("_prev_sid", "_marks", "_step_ann", "_legs")

    def step(self, name: str) -> None:
        t = _perf()
        if self._ann is not None:
            if self._step_ann is not None:
                self._step_ann.__exit__(None, None, None)
            self._step_ann = _TraceAnnotation(PREFIX + name)
            self._step_ann.__enter__()
        self._marks.append((name, t))

    def begin(self, name: str) -> None:
        ann = None
        if self._ann is not None:
            ann = _TraceAnnotation(PREFIX + name)
            ann.__enter__()
        self._legs.append((name, ann, _perf()))

    def end(self) -> None:
        self._end_leg(_perf())

    def _end_leg(self, t1: float) -> None:
        legs = self._legs
        name, ann, t0 = legs.pop()
        if ann is not None:
            ann.__exit__(None, None, None)
        parent = legs[-1][0] if legs else \
            self._marks[-1][0] if self._marks else self.name
        st = self._st
        st.buf.append((name, t0, t1, parent, st.solve_id, None))

    def __enter__(self):
        self._st = st = _state.frame
        self._prev_sid = st.solve_id
        st.solve_id = next(_SOLVE_IDS)
        st.stack.append(self)
        self._marks = []
        self._legs = []
        self._step_ann = self._ann = None
        if _profiling():
            self._ann = _TraceAnnotation(PREFIX + self.name)
            self._ann.__enter__()
        self._t0 = _perf()
        return self

    def __exit__(self, et, ev, tb):
        t1 = _perf()
        while self._legs:
            self._end_leg(t1)
        marks = self._marks
        if marks:
            if self._step_ann is not None:
                self._step_ann.__exit__(None, None, None)
            st = self._st
            append, sid = st.buf.append, st.solve_id
            ends = [t for _, t in marks[1:]]
            ends.append(t1)
            for (name, t0), end in zip(marks, ends):
                append((name, t0, end, self.name, sid, None))
        self._finish(t1)
        self._st.solve_id = self._prev_sid
        return False


class _SetupScope(Span):
    """A ``setup/`` span: also carries the JAX traces, trace seconds,
    backend compiles and compile seconds accrued while it was open."""

    __slots__ = ("_cw",)

    @staticmethod
    def _counters():
        from amgcl_tpu.telemetry import compile_watch as _cwatch
        if not _cwatch.enabled():
            return None
        return _cwatch.global_watch().counters()

    def __enter__(self):
        self._cw = self._counters()
        return Span.__enter__(self)

    def __exit__(self, et, ev, tb):
        if self._cw is not None:
            now = self._counters()
            self.set(traces=now[0] - self._cw[0],
                     trace_s=now[1] - self._cw[1],
                     compiles=now[2] - self._cw[2],
                     compile_s=now[3] - self._cw[3])
        return Span.__exit__(self, et, ev, tb)


def span(name: str, **attrs) -> Span:
    """Host span ``name`` (see the module docstring), recorded into
    :data:`RECORDER` when it closes; the caller may ``set`` more
    attributes while it is open."""
    cls = _SetupScope if name.startswith("setup/") else Span
    return cls(name, attrs or None)


def solve_span(**attrs) -> Span:
    """The ``solve`` span of one ``make_solver`` call."""
    return _SolveScope("solve", attrs or None)


class RequestSpans(SpanRecorder):
    """Bounded thread-safe recorder of per-request serve phases.

    ``add(request_id, phases)`` takes ``[(phase, start_s, end_s), ...]``
    in ``time.perf_counter()`` seconds and records ``serve/<phase>``
    spans; the export renders one ``reqNNNNN/phase`` complete event per
    span, same trace-event shape as ``Profiler.to_chrome_trace`` so the
    tracks merge on a shared epoch. Past ``max_events`` spans the oldest
    are evicted (the count is carried in the export) — a long-running
    service must not grow without bound."""

    CATEGORY = "amgcl/serve"

    def __init__(self, max_events: int = 100_000):
        super().__init__(max_events)

    @property
    def max_events(self) -> int:
        return self.max_spans

    @property
    def dropped(self) -> int:
        return self.evicted

    def add(self, request_id: int,
            phases: Sequence[Tuple[str, float, float]],
            label: str = "req") -> None:
        """``label`` prefixes the span path: per-request spans ride
        ``req<id>/...``, batch-shared phases (pad/compile/solve/sync are
        one device dispatch for the whole bucket) ride ``batch<id>/...``
        ONCE instead of B identical copies."""
        track = {"track": "%s%05d" % (label, int(request_id))}
        entries = [("serve/" + name, float(start), float(end), None, None,
                    track) for name, start, end in phases]
        self.record_many(entries)
        RECORDER.record_many(entries)

    def _path(self, entry: SpanRecord) -> str:
        return "%s/%s" % (entry[5]["track"], entry[0][len("serve/"):])

    @property
    def events(self) -> List[Tuple[str, float, float]]:
        """(path, start_s, end_s) per span — the Profiler.events triple."""
        return [(self._path(e), e[1], e[2]) for e in self.spans()]


#: thread-local holder of the profiler the CURRENT hierarchy build is
#: annotating into — lets deep setup stages (device MIS, Galerkin plan
#: construction, segment kernels) attribute themselves without threading
#: a profiler argument through every coarsening policy signature
_setup_tls = threading.local()


@contextmanager
def setup_scope(prof, name: str):
    """Setup-phase instrumentation in one wrapper: a
    ``span("setup/<name>")`` and a tic/toc scope on ``prof``
    (utils/profiler.Profiler — wall time, optionally device-synced; the
    tree behind ``AMG.setup_report()``). ``prof`` may be None (span
    only) — the numerics never depend on a profiler being attached.

    While the scope is open the profiler is published thread-locally so
    :func:`setup_substage` can attach nested stages from code that never
    sees the AMG builder (``<scope>/<substage>`` in the profile)."""
    prev = getattr(_setup_tls, "scope", None)
    _setup_tls.scope = (prof, name)
    try:
        with span("setup/" + name):
            if prof is None:
                yield
            else:
                with prof.scope(name):
                    yield
    finally:
        _setup_tls.scope = prev


@contextmanager
def setup_substage(name: str):
    """Nested setup stage under whatever :func:`setup_scope` is active
    on this thread (a bare span outside a build): device-MIS rounds,
    plan construction and the numeric segment kernels report through
    this, so ``AMG.setup_profile`` attributes the device-setup path
    stage by stage like the host path."""
    cur = getattr(_setup_tls, "scope", None)
    with span("setup/" + (cur[1] + "/" if cur else "") + name):
        if cur is None or cur[0] is None:
            yield
            return
        # Profiler scopes nest on a stack — the path renders as
        # "<parent>/<name>" without re-prefixing here
        with cur[0].scope(name):
            yield
