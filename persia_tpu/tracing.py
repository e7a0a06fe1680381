"""Stage-latency tracing, distributed trace context, and the flight recorder.

Parity target: the reference's pervasive `tracing::debug!` stage timers
around every pipeline hop (`rust/persia-core/src/forward.rs:591-593,665-669`,
`embedding_worker_service/mod.rs:909-938`) with the `LOG_LEVEL` env filter
(`rust/persia-core/src/lib.rs:463-465`).

Adds what the reference lacks:

- an in-memory ring of completed spans exported as **chrome://tracing /
  Perfetto JSON**, so a training-run timeline (lookup → stage → device step
  → grad return) is viewable alongside JAX's own profiler traces;
- a **trace context** (``trace_id/span_id/parent_id``), thread-local and
  generated at the edge, that rides the RPC frame header (negotiated
  capability, see ``service/rpc.py``) and the serving path's
  ``X-Trace-Id``/``X-Parent-Span`` HTTP headers — one id links a client
  request to the replica's cache probe, and a gradient batch to its
  journaled PS apply;
- a **flight recorder**: a bounded ring of structured events (breaker
  trips, quarantine/heal, resyncs, fence commits, injected chaos faults),
  each stamped with the active trace_id, dumped atomically on
  SIGTERM/atexit/uncaught-fatal so every chaos failure has a black box.

Usage::

    from persia_tpu import tracing

    tracing.enable()          # or PERSIA_TRACE=1; off by default
    with tracing.span("lookup", slot="cat_0"):
        ...
    tracing.trace_export("/tmp/trace.json")

Spans nest via a thread-local context stack; duration is also pushed to the
metrics Histogram ``persia_stage_duration_seconds`` when metrics are enabled.
A span on a disabled tracer records nothing in the ring — hot paths pay
~nothing by default. The flight recorder is always on (its events are rare
by construction); only the dump path needs arming.

Every span also opens a ``jax.profiler.TraceAnnotation`` of its name and
attributes, so that a profiler session in this process (``jax.profiler.
start_trace``) finds the program's stages as ``/host:CPU`` events on the
device trace's own clock. The annotation is inert while no session runs
(about half a microsecond) and there is no switch for it. The profiler is
taken from ``sys.modules``: a role that never imported JAX does not import
it for tracing. The two clocks differ by construction (the profiler counts
from its session's start, the ring stamps ``time.time()``); a span seen in
both, by name and ``seq``, is the anchor that places ring-only spans
(:func:`record_span`) on the device trace.

A live profiler session is also a window. A stage or wait span asks the
annotation class ``is_enabled()`` as it opens; one that opened inside a
session asks again as it closes, and if the session is still live adds its
busy or wait time to one process-wide :class:`StageAccumulator`, by the rule
of a thread's bound one (a work span's busy time leaves out the waits nested
in it). :func:`session_totals` gives the last session's tables and its
``wall_s`` (the first such span's start to the last counted span's end):
the program's stage and wait totals for exactly the seconds the device
trace holds host events for, to whoever opens a session on a live trainer
(``jax.profiler.start_trace``, the profiler server), without parsing the
trace. With no session a stage or wait span pays that one ``is_enabled()``
call (0.02 us here, under 0.1 us on the benchmark's host) and nothing else:
no object is kept, no lock taken, no callback registered.
"""

from __future__ import annotations

import atexit
import json
import os
import signal
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Deque, Dict, List, Optional, Tuple

from persia_tpu.logger import get_default_logger

logger = get_default_logger("persia_tpu.tracing")

_MAX_SPANS = int(os.environ.get("PERSIA_TRACE_BUFFER", "20000"))
_lock = threading.Lock()
_spans: Deque[Dict[str, Any]] = deque(maxlen=_MAX_SPANS)
_tls = threading.local()
# Opt-in, like the reference's LOG_LEVEL-gated stage timers: a span on a
# disabled tracer is a no-op, so hot paths pay ~nothing by default.
_enabled = os.environ.get("PERSIA_TRACE", "0") in ("1", "true")
_histogram = None
# Role tag stamped on exports/flight dumps so the fleet merger can name
# processes ("trainer0", "replica1", "gateway", ...). Set once per process.
_role = os.environ.get("PERSIA_ROLE", "")


def enable(on: bool = True) -> None:
    global _enabled
    _enabled = on


def enabled() -> bool:
    return _enabled


def set_role(role: str) -> None:
    """Tag this process's spans/flight dumps with a fleet role name."""
    global _role
    _role = role


def get_role() -> str:
    return _role or f"proc_{os.getpid()}"


def _get_histogram():
    global _histogram
    if _histogram is None:
        try:
            from persia_tpu.metrics import get_metrics

            _histogram = get_metrics().histogram(
                "persia_stage_duration_seconds", "per-stage latency"
            )
        except Exception:
            _histogram = False
    return _histogram


# --------------------------------------------------------------------- context
#
# The thread-local stack holds (trace_id, span_id) frames. ``span`` pushes a
# frame for its own id; ``trace_context`` pushes an adopted frame carrying a
# REMOTE parent (what arrived on the wire), so spans opened beneath it become
# children of the caller's span in the merged timeline. The stack works even
# when tracing is disabled — adoption is cheap and the flight recorder wants
# the ambient trace_id regardless — but ``span`` itself never touches it on
# the disabled path.

def _gen_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_context() -> Optional[Tuple[str, Optional[str]]]:
    """The ambient ``(trace_id, span_id)`` to propagate to a downstream hop,
    or ``None`` when no trace is active on this thread."""
    st = getattr(_tls, "stack", None)
    if st:
        return st[-1]
    return None


def current_trace_id() -> Optional[str]:
    ctx = current_context()
    return ctx[0] if ctx else None


@contextmanager
def trace_context(trace_id: Optional[str] = None,
                  parent_span: Optional[str] = None):
    """Open (edge) or adopt (wire) a trace scope on this thread.

    With no arguments a fresh ``trace_id`` is generated — this is the edge.
    With ids parsed off a frame/header, spans beneath become children of the
    remote caller's span. Yields the ``(trace_id, parent_span)`` frame."""
    st = _stack()
    frame = (trace_id or _gen_id(16), parent_span)
    st.append(frame)
    try:
        yield frame
    finally:
        st.pop()


def wire_headers() -> Dict[str, str]:
    """HTTP headers carrying the ambient context (empty when none active)."""
    ctx = current_context()
    if ctx is None:
        return {}
    h = {"X-Trace-Id": ctx[0]}
    if ctx[1]:
        h["X-Parent-Span"] = ctx[1]
    return h


# jax.profiler.TraceAnnotation, once this process has imported JAX
_annotation = None


def _trace_annotation():
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None  # asked again at the next span: JAX may come later
        try:
            _annotation = jax.profiler.TraceAnnotation
        except AttributeError:
            return None  # JAX is still being imported
    return _annotation


class StageAccumulator:
    """Totals of the stage spans closed on the threads bound to it
    (:func:`accumulate`): ``stages[name] = {n, busy_s, max_s}`` for work
    (:func:`stage_span`) and ``waits[name] = {n, wait_s, max_s}`` for
    waits (:func:`wait_span`). A work span's busy time leaves out the
    waits nested in it. ``clock`` is what the bound threads' stage spans
    are timed by."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self._lock = threading.Lock()
        self.stages: Dict[str, Dict[str, float]] = {}
        self.waits: Dict[str, Dict[str, float]] = {}

    def add(self, name: str, secs: float, wait: bool = False) -> None:
        table, key = (self.waits, "wait_s") if wait else (self.stages, "busy_s")
        with self._lock:
            row = table.get(name)
            if row is None:
                row = table[name] = {"n": 0, key: 0.0, "max_s": 0.0}
            row["n"] += 1
            row[key] += secs
            if secs > row["max_s"]:
                row["max_s"] = secs

    def busy_s(self, *names: str) -> float:
        with self._lock:
            return sum(self.stages[n]["busy_s"] for n in names if n in self.stages)


@contextmanager
def accumulate(acc: Optional[StageAccumulator]):
    """Bind ``acc`` to this thread: stage and wait spans closed on it
    while bound add to ``acc``."""
    prev = getattr(_tls, "acc", None)
    _tls.acc = acc
    try:
        yield acc
    finally:
        _tls.acc = prev


# The profiler session as a window: the totals of the stage and wait spans
# that opened and closed inside the last ``jax.profiler`` session this
# process's spans saw, with the session's extent as they saw it (the first
# such span's start, the last counted span's end, on ``time.perf_counter``).
_session: Optional[StageAccumulator] = None
_session_live = False  # what the last stage or wait span to ask was told
_session_t0 = _session_t1 = 0.0


def _session_seen(live: bool) -> Optional[StageAccumulator]:
    """A stage or wait span (or :func:`session_totals`) found a profiler
    session live, or found none where the last one to ask found one: a
    session that begins gets a fresh accumulator, one that ends keeps its
    totals for the reader. Returns the live session's accumulator."""
    global _session, _session_live, _session_t0, _session_t1
    with _lock:
        if live and not _session_live:
            _session = StageAccumulator()
            _session_t0 = _session_t1 = time.perf_counter()
        _session_live = live
        return _session if live else None


def session_totals() -> Optional[Dict[str, Any]]:
    """``{"wall_s", "stages": {name: {n, busy_s, max_s}}, "waits": {name:
    {n, wait_s, max_s}}}`` of the last profiler session (live or ended)
    that a stage or wait span of this process saw, or ``None`` where none
    was: ``stream_stats()``'s two tables over every thread, for exactly
    the seconds the device trace holds host events for. A span that
    straddles either end of the session counts for nothing. Two sessions
    are told apart by a stage or wait span, or a call of this function,
    that found the profiler off between them."""
    if _session_live:
        ann = _trace_annotation()
        if ann is None or not ann.is_enabled():
            _session_seen(False)
    with _lock:
        acc, wall_s = _session, _session_t1 - _session_t0
    if acc is None:
        return None
    with acc._lock:
        return {"wall_s": wall_s,
                "stages": {k: dict(v) for k, v in acc.stages.items()},
                "waits": {k: dict(v) for k, v in acc.waits.items()}}


_PLAIN, _STAGE, _WAIT = 0, 1, 2


class _Span:
    """The one span primitive. Every kind opens a profiler annotation and,
    while the tracer is enabled, records itself in the ring; the stage
    kinds also feed the stage histogram, the thread's accumulator and,
    while a profiler session is live, the session's."""

    __slots__ = ("name", "attrs", "kind", "_ann", "_ring", "_acc", "_clock", "_t0", "_w0",
                 "_sess")

    def __init__(self, name: str, attrs: Dict[str, Any], kind: int):
        self.name, self.attrs, self.kind = name, attrs, kind

    def __enter__(self):
        ann = _trace_annotation()
        if ann is not None:
            ann = ann(self.name, **self.attrs)
            ann.__enter__()
        self._ann = ann
        self._ring = None
        if _enabled:
            st = _stack()
            if st:
                trace_id, parent = st[-1]
            else:
                trace_id, parent = _gen_id(16), None  # this span IS the edge
            span_id = _gen_id(8)
            st.append((trace_id, span_id))
            self._ring = (trace_id, span_id, parent, time.time() * 1e6)
        if self.kind:
            acc = self._acc = getattr(_tls, "acc", None)
            self._clock = time.perf_counter if acc is None else acc.clock
            self._w0 = getattr(_tls, "wait_s", 0.0)
            self._sess = None
            if ann is not None:
                live = ann.is_enabled()
                if live or _session_live:
                    self._sess = _session_seen(live)
            self._t0 = self._clock()
        elif self._ring is not None:
            self._clock = time.perf_counter
            self._t0 = self._clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self.kind or self._ring is not None:
            dur = self._clock() - self._t0
            if self.kind:
                acc, sess = self._acc, self._sess
                wait = self.kind == _WAIT
                if wait:
                    _tls.wait_s = self._w0 + dur
                if acc is not None or sess is not None:
                    # a work span's busy time leaves out the waits nested in it
                    secs = dur if wait else dur - (getattr(_tls, "wait_s", 0.0) - self._w0)
                    if acc is not None:
                        acc.add(self.name, secs, wait)
                    if sess is not None:
                        self._to_session(secs, dur)
            if self._ring is not None:
                self._record(dur)
            h = _get_histogram()
            if h:
                h.observe(dur, stage=self.name)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        return False

    def set(self, **attrs) -> None:
        """Attributes known only once the work is done (a count, the
        slowest part): set before the span closes."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    def _to_session(self, secs: float, dur: float) -> None:
        """The span opened inside a profiler session: it counts for that
        session if it closes inside it too (and was timed by the
        session's clock, as every span is but a test's)."""
        global _session_t1
        if not self._ann.is_enabled():
            _session_seen(False)
        elif self._sess is _session and self._clock is time.perf_counter:
            self._sess.add(self.name, secs, self.kind == _WAIT)
            with _lock:
                _session_t1 = max(_session_t1, self._t0 + dur)

    def _record(self, dur: float) -> None:
        trace_id, span_id, parent, ts_us = self._ring
        st = _stack()
        st.pop()
        logger.debug("%s%s took %.3f ms %s", "  " * len(st), self.name,
                     dur * 1e3, self.attrs if self.attrs else "")
        args = {k: str(v) for k, v in self.attrs.items()}
        args["trace_id"] = trace_id
        args["span_id"] = span_id
        if parent:
            args["parent_id"] = parent
        with _lock:
            _spans.append({
                "name": self.name,
                "ph": "X",
                "ts": ts_us,
                "dur": dur * 1e6,
                "pid": os.getpid(),
                "tid": threading.get_ident() % 2**31,
                "args": args,
            })


def span(name: str, **attrs) -> _Span:
    """Time a pipeline stage; logs at debug level, records for export."""
    return _Span(name, attrs, _PLAIN)


def record_span(name: str, dur_s: float, **attrs) -> None:
    """Record a span whose duration was measured EXTERNALLY (e.g. the
    native sharded-feed walker reports per-shard walk ns from inside the
    thread pool — wrapping the ctypes call in :func:`span` would time the
    whole dispatch, not the shard). The span ends "now"; its start is
    back-dated by the given duration. No-op when tracing is off."""
    if not _enabled:
        return
    st = _stack()
    if st:
        trace_id, parent = st[-1]
    else:
        trace_id, parent = _gen_id(16), None
    args = {k: str(v) for k, v in attrs.items()}
    args["trace_id"] = trace_id
    args["span_id"] = _gen_id(8)
    if parent:
        args["parent_id"] = parent
    with _lock:
        _spans.append({
            "name": name,
            "ph": "X",
            "ts": time.time() * 1e6 - dur_s * 1e6,
            "dur": dur_s * 1e6,
            "pid": os.getpid(),
            "tid": threading.get_ident() % 2**31,
            "args": args,
        })
    h = _get_histogram()
    if h:
        h.observe(dur_s, stage=name)


def stage_span(name: str, **attrs) -> _Span:
    """Pipeline-stage timer that ALWAYS feeds the live stage histogram
    (``persia_stage_duration_seconds{stage=...}``) and the accumulator
    bound to the thread (:func:`accumulate`), and records a trace span
    only when tracing is enabled. The sanctioned replacement for hand-rolled
    ``t0 = time.time()`` stage timers in pipeline modules (persia-lint
    OBS002); the bench reads the same series the trace viewer shows."""
    return _Span(name, attrs, _STAGE)


def wait_span(name: str, **attrs) -> _Span:
    """:func:`stage_span` for time a thread spends blocked on another
    (a full or empty queue, a ring, the device): accounted as ``waits``
    and left out of the busy time of the stage spans around it."""
    return _Span(name, attrs, _WAIT)


def spans_snapshot() -> list:
    with _lock:
        return list(_spans)


def spans_drain() -> list:
    """Snapshot AND clear the ring in one lock hold — the ``/spans``
    endpoint uses this so the fleet collector never double-counts."""
    with _lock:
        out = list(_spans)
        _spans.clear()
    return out


def clear() -> None:
    with _lock:
        _spans.clear()


def _atomic_write_json(path: str, doc: Dict[str, Any]) -> None:
    """temp + fsync + rename: the artifact never exists half-written (the
    same durable-write discipline persia-lint DUR001 polices elsewhere)."""
    data = json.dumps(doc).encode()
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def export_doc(events: Optional[List[Dict[str, Any]]] = None) -> Dict[str, Any]:
    """The per-role export document: trace events plus the clock/role
    metadata the fleet merger needs to align and name this process."""
    return {
        "traceEvents": spans_snapshot() if events is None else events,
        "displayTimeUnit": "ms",
        "metadata": {
            "role": get_role(),
            "pid": os.getpid(),
            "clock_unix_us": time.time() * 1e6,
        },
    }


def trace_export(path: str) -> int:
    """Write the span ring as chrome://tracing JSON; returns span count."""
    doc = export_doc()
    _atomic_write_json(path, doc)
    n = len(doc["traceEvents"])
    logger.info("exported %d trace events to %s", n, path)
    return n


# ------------------------------------------------------------ flight recorder
#
# A bounded ring of structured events — the black box. Unlike spans it is
# ALWAYS on: the events it records (breaker trips, quarantine/heal, resyncs,
# fence commits, injected chaos faults) are rare by construction, so the
# cost is one dict build + deque append per event. Each event is stamped
# with the ambient trace_id so a chaos fault can be correlated with the
# request/batch it hit. ``install_flight_recorder`` arms an atomic dump on
# SIGTERM, atexit, and uncaught fatal exceptions.

_FLIGHT_MAX = int(os.environ.get("PERSIA_FLIGHT_BUFFER", "4096"))
_flight_lock = threading.Lock()
_flight: Deque[Dict[str, Any]] = deque(maxlen=_FLIGHT_MAX)
_flight_seq = 0
_flight_path: Optional[str] = None
_flight_installed = False


def record_event(kind: str, **attrs) -> Dict[str, Any]:
    """Append a structured event to the flight ring (always on)."""
    global _flight_seq
    evt = {
        "kind": kind,
        "ts_us": time.time() * 1e6,
        "pid": os.getpid(),
        "tid": threading.get_ident() % 2**31,
        "trace_id": current_trace_id(),
        "attrs": {k: str(v) for k, v in attrs.items()},
    }
    with _flight_lock:
        evt["seq"] = _flight_seq
        _flight_seq += 1
        _flight.append(evt)
    return evt


def flight_snapshot() -> list:
    with _flight_lock:
        return list(_flight)


def flight_clear() -> None:
    global _flight_seq
    with _flight_lock:
        _flight.clear()
        _flight_seq = 0


def flight_dump(path: Optional[str] = None) -> Optional[str]:
    """Atomically write the flight ring (and its metadata) to ``path`` or
    the armed path; returns the path written, or None when unarmed."""
    target = path or _flight_path
    if not target:
        return None
    doc = {
        "role": get_role(),
        "pid": os.getpid(),
        "dumped_unix_us": time.time() * 1e6,
        "events": flight_snapshot(),
    }
    _atomic_write_json(target, doc)
    return target


def _dump_quietly() -> None:
    try:
        flight_dump()
    except Exception:  # noqa: BLE001 — a failing black box must not mask the crash
        pass
    if _export_path:
        try:
            # write directly (no logging): at interpreter exit the log
            # streams may already be closed, and logging then prints a
            # "--- Logging error ---" traceback over the real output
            _atomic_write_json(_export_path, export_doc())
        except Exception:  # noqa: BLE001
            pass


_export_path: Optional[str] = None
_export_armed = False


def arm_trace_export(path: str) -> None:
    """Arm a span-ring export to ``path`` at interpreter exit AND alongside
    any flight dump (SIGTERM / fatal excepthook) — a terminated role still
    leaves its timeline behind for the fleet merger's dead-role fallback."""
    global _export_path, _export_armed
    _export_path = path
    if not _export_armed:
        _export_armed = True
        atexit.register(_dump_quietly)


def install_flight_recorder(path: str) -> None:
    """Arm the flight recorder to dump to ``path`` on SIGTERM, interpreter
    exit, and uncaught fatal exceptions. Chains any handlers already
    installed (topology roles install their own SIGTERM shutdown first)."""
    global _flight_path, _flight_installed
    _flight_path = path
    if _flight_installed:
        return
    _flight_installed = True
    atexit.register(_dump_quietly)

    prev_hook = sys.excepthook

    def hook(exc_type, exc, tb):
        record_event("fatal", exc=f"{exc_type.__name__}: {exc}")
        _dump_quietly()
        prev_hook(exc_type, exc, tb)

    sys.excepthook = hook

    try:
        prev_term = signal.getsignal(signal.SIGTERM)

        def on_term(signum, frame):
            record_event("sigterm")
            _dump_quietly()
            if callable(prev_term):
                prev_term(signum, frame)
            else:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signal.SIGTERM)

        signal.signal(signal.SIGTERM, on_term)
    except ValueError:
        pass  # not the main thread: atexit + excepthook still cover us
