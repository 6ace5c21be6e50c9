"""Span tracing: one span system, on the device trace's clock.

:func:`span` is the single span entry point of the program. Every span
it opens does three things:

  * it enters a ``jax.profiler.TraceAnnotation`` of the same name, so it
    lands on the profiler's ``/host:CPU`` plane beside the device's
    operations whenever a profiler trace is active (free otherwise);
  * it adds its host wall seconds to this thread's
    :func:`collect_stage_times` sinks, which the benchmark's per-layer
    readers fold (``profiler.stage`` is a one-line delegate);
  * when a :class:`Tracer` is installed, it records a parent-linked
    :class:`Span` with its attributes — admission → replay → per-window
    wait/transfer/dispatch/sync → finalize — plus point events (retry,
    degrade, watchdog timeout, resume) attached to the span they
    happened under.

The clock is the profiler's: ``time.time_ns()`` (CLOCK_REALTIME), which
the xplane host plane also stamps. An xplane event starts at the trace's
``profile_start_time`` stat plus its ``start_ns``; a :class:`Span` starts
at ``t0_ns``. So a query's Chrome export (``trace_path``) and an
``.xplane.pb`` of the same run overlay without an offset.

The Chrome export is trace-event JSON (``ph: "X"`` complete events +
``ph: "i"`` instants), loadable in Perfetto / ``chrome://tracing``, per
process (:meth:`Tracer.export_chrome`) or per query (filter by the root
span's ``trace_id``; ``DatasetSession.query(trace_path=...)`` does
this).

Cost: with no tracer installed a span is one ``TraceAnnotation`` (well
under a microsecond) plus two clock reads when stage sinks are active;
no dict, no lock. Released values are bit-identical with tracing on or
off (spans read clocks, never data or keys; pinned by
tests/obs_serving_test.py).

Enabling the tracer: install programmatically
(``trace.install(trace.Tracer())``) or set ``PIPELINEDP_TPU_TRACE=<path>``
— a tracer is installed at import and the process trace is written to
``<path>`` at exit (a directory gets ``trace_<pid>.json`` inside it;
``1`` installs without writing).

Cross-thread spans and sinks: the current span and the sink list are
thread-local; worker threads (watchdog query runner, slab prefetch pool)
join their parent's tree with ``with trace.attach(parent_span):`` and its
sinks with ``with trace.adopt_sinks(parent_sinks):``.

Thread-safety contract of the sinks (checked by dplint DPL008):
:func:`span`, :func:`current_sinks` and :func:`adopt_sinks` are
pool-safe — sink mutation funnels through ``_add_stage_time`` under
``_sink_lock``, and the sink *list* is thread-local.
:func:`collect_stage_times` is owning-thread only. Set
``PIPELINEDP_TPU_DEBUG_LOCKS=1`` to assert the sink lock is held around
every sink mutation.

DP-safety: span names are static strings; attribute and event payloads
go through :func:`~pipelinedp_tpu.obs.metrics.check_safe_value` — raw
pids, partition keys, pre-noise values and any array are refused at the
API (TelemetryLeakError), and dplint DPL011 flags offending call sites
statically.
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import dataclasses
import itertools
import json
import os
import tempfile
import threading
import time
from typing import Deque, Dict, Iterator, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from pipelinedp_tpu.obs import flight as flight_lib
from pipelinedp_tpu.obs import metrics as metrics_lib

TRACE_ENV = "PIPELINEDP_TPU_TRACE"
# Debug-locks env knob: name kept here, validation delegated to the
# shared loader.env_int helper (unset/empty -> off; junk raises).
DEBUG_LOCKS_ENV = "PIPELINEDP_TPU_DEBUG_LOCKS"

# Bounded finished-span buffer: a long-lived serving process must not
# grow its trace without bound; the newest spans win (the ones an
# operator debugging "why was that query slow" wants).
MAX_SPANS = 200_000


@dataclasses.dataclass
class Span:
    """One finished (or in-flight) span. Times are ``time.time_ns()``,
    the profiler host plane's clock."""
    name: str
    span_id: int
    parent_id: Optional[int]
    trace_id: int
    thread_id: int
    t0_ns: int
    dur_ns: int = -1  # -1 while in flight
    attrs: Dict[str, object] = dataclasses.field(default_factory=dict)
    events: List[Tuple[str, int, Dict[str, object]]] = dataclasses.field(
        default_factory=list)

    def set_attribute(self, key: str, value) -> None:
        metrics_lib.check_safe_value(key, value)
        self.attrs[key] = value

    def add_event(self, name: str, **attrs) -> None:
        for k, v in attrs.items():
            metrics_lib.check_safe_value(k, v)
        self.events.append((name, time.time_ns(), dict(attrs)))


# -- stage-time sinks ---------------------------------------------------------
#
# Active wall-clock stage collectors (see collect_stage_times). Thread-local
# so concurrent engines don't interleave their phase budgets; worker pools
# an engine spawns (slab prefetch, encode workers) join their parent's
# collectors explicitly via adopt_sinks(current_sinks()). Sink updates are
# guarded by _sink_lock — multiple threads record into one sink dict.
_collect = threading.local()
_sink_lock = threading.Lock()


def _debug_locks() -> bool:
    """Re-read per call so tests can flip the env between stages."""
    from pipelinedp_tpu.native import loader
    return bool(loader.env_int(DEBUG_LOCKS_ENV, 0, 0, 1))


def current_sinks() -> list:
    """This thread's active stage-time sinks (share with adopt_sinks).
    Pool-safe: returns a fresh list snapshot of thread-local state."""
    return list(getattr(_collect, "sinks", None) or ())


def _add_stage_time(sinks, name: str, dt: float) -> None:
    """Thread-safe accumulation of one stage timing into the sinks —
    the single place sink dicts are mutated; every caller (any thread)
    goes through the lock acquired here."""
    with _sink_lock:
        if _debug_locks():
            assert _sink_lock.locked(), (
                "sink mutation outside _sink_lock — a refactor bypassed "
                "_add_stage_time's locking")
        for sink in sinks:
            sink[name] = sink.get(name, 0.0) + dt


@contextlib.contextmanager
def adopt_sinks(sinks) -> "Iterator[None]":
    """Installs a parent thread's collectors into this (worker) thread so
    its span timings merge into the parent's collect_stage_times() dict.
    Restores the worker's previous sinks on exit; safe to nest.
    Pool-safe: the handoff half of the cross-thread protocol — capture
    ``current_sinks()`` on the parent, enter this on the worker."""
    prev = getattr(_collect, "sinks", None)
    mine = list(prev or ())
    mine.extend(s for s in sinks if s not in mine)
    _collect.sinks = mine
    try:
        yield
    finally:
        _collect.sinks = prev


@contextlib.contextmanager
def collect_stage_times() -> Iterator[Dict[str, float]]:
    """Collects per-span host wall seconds for the enclosed block.

    Yields a dict that fills as spans complete: {span_name: seconds},
    summed over re-entries, nested spans included in their parents.
    These are HOST wall times — a span that only dispatches async device
    work (device_put, jitted kernels) is cheap here even when the device
    is busy long after.

    Owning-thread only: registers the sink on the *calling* thread's
    collector list; pool workers join through
    ``adopt_sinks(current_sinks())`` captured on this thread instead of
    entering this context themselves.
    """
    sink: Dict[str, float] = {}
    sinks = getattr(_collect, "sinks", None)
    if sinks is None:
        sinks = _collect.sinks = []
    sinks.append(sink)
    try:
        yield sink
    finally:
        sinks.remove(sink)


class _SpanCtx:
    """One span (module docstring): the profiler annotation, the stage
    sinks and, with a tracer, the recorded :class:`Span`. Yields the
    Span, or None when no tracer records it."""

    __slots__ = ("_name", "_tracer", "_parent", "_attrs", "_annotation",
                 "_sinks", "_t0_ns", "_span")

    def __init__(self, name: str, tracer: Optional["Tracer"],
                 parent: Optional[Span], attrs: Dict[str, object]):
        if tracer is not None:
            for k, v in attrs.items():
                metrics_lib.check_safe_value(k, v)
        self._name = name
        self._tracer = tracer
        self._parent = parent
        self._attrs = attrs

    def __enter__(self) -> Optional[Span]:
        self._sinks = getattr(_collect, "sinks", None) or None
        self._span = None
        if self._tracer is not None:
            self._span = self._tracer._start(self._name, self._parent,
                                             self._attrs)
            self._t0_ns = self._span.t0_ns
        elif self._sinks is not None:
            self._t0_ns = time.time_ns()
        self._annotation = TraceAnnotation(self._name)
        self._annotation.__enter__()
        return self._span

    def __exit__(self, exc_type, exc_val, exc_tb) -> None:
        self._annotation.__exit__(exc_type, exc_val, exc_tb)
        if self._sinks is None and self._span is None:
            return
        dur_ns = time.time_ns() - self._t0_ns
        if self._sinks is not None:
            _add_stage_time(self._sinks, self._name, dur_ns * 1e-9)
        if self._span is not None:
            self._tracer._finish(self._span, dur_ns,
                                 failed=exc_type is not None)


class Tracer:
    """Thread-safe span recorder (module docstring)."""

    def __init__(self, max_spans: int = MAX_SPANS):
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._tls = threading.local()
        self._finished: Deque[Span] = collections.deque(maxlen=max_spans)

    # -- span lifecycle ---------------------------------------------------

    def _stack(self) -> List[Span]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def current(self) -> Optional[Span]:
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    def span(self, name: str, parent: Optional[Span] = None,
             **attrs) -> _SpanCtx:
        """A new span under ``parent`` (default: this thread's current
        span when it is entered; None makes a root). Use as a context
        manager."""
        return _SpanCtx(name, self, parent, attrs)

    def _start(self, name: str, parent: Optional[Span],
               attrs: Dict[str, object]) -> Span:
        if parent is None:
            parent = self.current()
        with self._lock:
            span_id = next(self._ids)
        sp = Span(
            name=name, span_id=span_id,
            parent_id=parent.span_id if parent is not None else None,
            trace_id=parent.trace_id if parent is not None else span_id,
            thread_id=threading.get_ident(),
            t0_ns=time.time_ns(), attrs=dict(attrs))
        self._stack().append(sp)
        return sp

    def _finish(self, span: Span, dur_ns: int, failed: bool) -> None:
        span.dur_ns = dur_ns
        if failed:
            span.attrs.setdefault("error", True)
        stack = self._stack()
        if stack and stack[-1] is span:
            stack.pop()
        else:  # defensive: unbalanced exit never corrupts other spans
            try:
                stack.remove(span)
            except ValueError:
                pass
        with self._lock:
            self._finished.append(span)

    def event(self, name: str, **attrs) -> None:
        """Attaches a point event to the current span (dropped when no
        span is open — events without context have no tree to hang on)."""
        cur = self.current()
        if cur is not None:
            cur.add_event(name, **attrs)

    @contextlib.contextmanager
    def attach(self, parent: Optional[Span]):
        """Installs ``parent`` as this thread's current span so spans
        opened here join the parent's tree (cross-thread handoff)."""
        if parent is None:
            yield
            return
        stack = self._stack()
        stack.append(parent)
        try:
            yield
        finally:
            if stack and stack[-1] is parent:
                stack.pop()

    # -- export -----------------------------------------------------------

    def spans(self, trace_id: Optional[int] = None) -> List[Span]:
        with self._lock:
            out = list(self._finished)
        if trace_id is not None:
            out = [s for s in out if s.trace_id == trace_id]
        return out

    def export_chrome(self, trace_id: Optional[int] = None) -> dict:
        """Chrome trace-event JSON object (Perfetto-loadable):
        ``{"traceEvents": [...], "displayTimeUnit": "ms"}``. Span
        timestamps are microseconds since the Unix epoch on the profiler
        host plane's clock; parent links ride ``args.span_id`` /
        ``args.parent_id``."""
        pid = os.getpid()
        events = []
        for s in self.spans(trace_id):
            args = {"span_id": s.span_id, "trace_id": s.trace_id}
            if s.parent_id is not None:
                args["parent_id"] = s.parent_id
            args.update(s.attrs)
            events.append({
                "name": s.name, "ph": "X", "pid": pid,
                "tid": s.thread_id, "ts": s.t0_ns / 1000.0,
                "dur": max(s.dur_ns, 0) / 1000.0, "args": args,
            })
            for ev_name, ts_ns, ev_attrs in s.events:
                events.append({
                    "name": ev_name, "ph": "i", "s": "t", "pid": pid,
                    "tid": s.thread_id, "ts": ts_ns / 1000.0,
                    "args": dict(ev_attrs, span_id=s.span_id),
                })
        events.sort(key=lambda e: e["ts"])
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write_chrome(self, path: str,
                     trace_id: Optional[int] = None) -> str:
        """Writes the Chrome trace JSON to ``path`` (a directory gets
        ``trace_<pid>.json`` inside it); returns the file path."""
        if os.path.isdir(path):
            path = os.path.join(path, f"trace_{os.getpid()}.json")
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        # Atomic publish: a reader (or crash) never sees a torn trace.
        fd, tmp = tempfile.mkstemp(dir=parent or ".", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(self.export_chrome(trace_id), f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise
        return path

    def reset(self) -> None:
        with self._lock:
            self._finished.clear()


# -- the process-global tracer ----------------------------------------------

_active: Optional[Tracer] = None
_NULL_CTX = contextlib.nullcontext(None)


def install(tracer: Optional[Tracer] = None) -> Tracer:
    """Installs (and returns) the process tracer; spans start recording
    on every instrumented path."""
    global _active
    if tracer is None:
        tracer = Tracer()
    _active = tracer
    return tracer


def shutdown() -> None:
    """Uninstalls the process tracer; span()/event() return to no-ops."""
    global _active
    _active = None


def active() -> Optional[Tracer]:
    return _active


def enabled() -> bool:
    return _active is not None


def span(name: str, parent: Optional[Span] = None, **attrs) -> _SpanCtx:
    """The program's span entry point (module docstring): annotates the
    profiler trace, feeds the stage sinks, and records a Span (yielded
    by ``with``) when a tracer is installed; yields None otherwise."""
    return _SpanCtx(name, _active, parent, attrs)


def event(name: str, **attrs) -> None:
    # Every span event also lands in the always-on flight recorder
    # (obs/flight.py): the retry/degrade/evict/hit vocabulary is exactly
    # the post-mortem an operator wants from a dead process, and it must
    # exist with no tracer installed.
    flight_lib.record(name, **attrs)
    t = _active
    if t is not None:
        t.event(name, **attrs)


def current() -> Optional[Span]:
    t = _active
    return t.current() if t is not None else None


def attach(parent: Optional[Span]):
    t = _active
    if t is None or parent is None:
        return _NULL_CTX
    return t.attach(parent)


def _init_from_env() -> None:
    path = os.environ.get(TRACE_ENV, "")
    if not path or path == "0":
        return
    tracer = install()
    if path != "1":
        atexit.register(lambda: tracer.write_chrome(path))


_init_from_env()
