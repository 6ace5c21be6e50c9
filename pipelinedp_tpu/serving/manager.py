"""SessionManager: a fleet of resident datasets under one budget, with
admission control, per-query deadlines, and an LRU demotion ladder.

One ``DatasetSession`` is a dataset; a serving process holds many. This
module is the fleet layer (SERVING.md "Fleet operation"):

  * **Residency budget** — every admitted session's bytes (device copy,
    host slab, bound cache) count against ONE global budget. When an
    admit or re-hydration overflows it, least-recently-used sessions
    demote down the ladder: device-resident → host slab
    (``demote_device``) → disk spill (``spill`` through the
    ``SessionStore``) → on-demand re-hydration at their next query.
    Sessions with queries in flight are never demoted past their slab.
  * **Admission control** — a bounded in-flight gate: a query arriving
    while ``max_inflight`` queries are executing is *shed* with a typed
    :class:`SessionOverloadedError` (it never queues, so latency under
    overload is bounded by the gate, not by an unbounded backlog).
  * **Deadlines** — the manager's ``default_deadline_s`` (or
    ``PIPELINEDP_TPU_QUERY_DEADLINE_S``) rides every query of a managed
    session: the slab driver checks it between windows and the whole
    replay runs under a DispatchWatchdog, so even a wedged replay
    surfaces as a retryable ``QueryDeadlineError`` within the deadline.

The manager is thread-safe; its lock is never held while another
session's lifecycle lock is awaited *and* vice versa (sessions notify
the manager only after releasing their own lifecycle lock), so query
threads and demotion sweeps cannot deadlock.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, List, Optional

from pipelinedp_tpu import profiler
from pipelinedp_tpu.obs import metrics as obs_metrics
from pipelinedp_tpu.obs import ops_plane as ops_plane_lib
from pipelinedp_tpu.obs import trace as obs_trace
from pipelinedp_tpu.serving import session as session_lib
from pipelinedp_tpu.serving import store as store_lib

# Tuning knobs (README "Tuning knobs" + SERVING.md):
#   PIPELINEDP_TPU_SERVING_INFLIGHT — max concurrently executing
#     queries across the fleet before shedding (default 8).
INFLIGHT_ENV = "PIPELINEDP_TPU_SERVING_INFLIGHT"

# Fleet profiler event counters (profiler.count_event / event_count):
EVENT_DEMOTIONS = "serving/sessions_demotions"
EVENT_SPILLS = "serving/sessions_spills"
EVENT_SHED = "serving/queries_shed"
# serving/sessions_rehydrations is credited by session.rehydrate
# (session_lib.EVENT_REHYDRATIONS) so un-managed rehydrations count too.


def max_inflight_default() -> int:
    """Validated PIPELINEDP_TPU_SERVING_INFLIGHT (default 8)."""
    from pipelinedp_tpu.native import loader
    return loader.env_int(INFLIGHT_ENV, 8, 1, 1 << 16)


class SessionOverloadedError(RuntimeError):
    """The in-flight query gate is full: this query is shed, not queued.

    Typed load shedding is the overload contract (SERVING.md): the
    caller retries with backoff or routes elsewhere; the serving
    process never accumulates an unbounded backlog behind a slow or
    wedged query."""

    def __init__(self, inflight: int, max_inflight: int):
        super().__init__(
            f"serving overloaded: {inflight} queries in flight (gate "
            f"{max_inflight}); query shed — retry with backoff")
        self.inflight = inflight
        self.max_inflight = max_inflight


def fleet_counters(manager: Optional["SessionManager"] = None
                   ) -> Dict[str, int]:
    """Snapshot of the fleet counters.
    ``sessions_resident``/``sessions_spilled`` are gauges of the given
    manager; the rest are process-wide monotonic counters."""
    out = {
        "demotions": profiler.event_count(EVENT_DEMOTIONS),
        "spills": profiler.event_count(EVENT_SPILLS),
        "rehydrations": profiler.event_count(
            session_lib.EVENT_REHYDRATIONS),
        "queries_shed": profiler.event_count(EVENT_SHED),
        "query_deadline_hits": profiler.event_count(
            session_lib.EVENT_DEADLINE_HITS),
        "device_fallbacks": profiler.event_count(
            session_lib.EVENT_DEVICE_FALLBACKS),
        "bound_cache_corrupt_dropped": profiler.event_count(
            store_lib.EVENT_BOUND_DROPPED),
    }
    from pipelinedp_tpu.serving import fleet as fleet_lib
    out["fleet"] = fleet_lib.fleet_counters()
    if manager is not None:
        with manager._lock:
            sessions = list(manager._sessions.values())
        out["sessions_resident"] = sum(1 for s in sessions
                                       if not s.is_spilled)
        out["sessions_spilled"] = sum(1 for s in sessions if s.is_spilled)
    return out


class SessionManager:
    """Admits DatasetSessions under one residency budget (module doc).

    store: the SessionStore backing the spill rung (and ``open``);
      defaults to ``SessionStore()`` (PIPELINEDP_TPU_SESSION_DIR).
    budget_bytes: the global residency budget across all admitted
      sessions; defaults to PIPELINEDP_TPU_RESIDENT_BYTES.
    max_inflight: the admission gate width
      (PIPELINEDP_TPU_SERVING_INFLIGHT).
    default_deadline_s: per-query deadline for managed sessions; None
      defers to PIPELINEDP_TPU_QUERY_DEADLINE_S (0 = none).
    ops_port: starts the observability endpoint (obs/ops_plane.py:
      /metrics, /healthz, /statusz, /debug/flightz) over this manager —
      0 binds an ephemeral port; None defers to
      PIPELINEDP_TPU_OPS_PORT (unset/0 = no endpoint). ``close()``
      stops it.
    """

    def __init__(self, store: Optional[store_lib.SessionStore] = None, *,
                 budget_bytes: Optional[int] = None,
                 max_inflight: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 ops_port: Optional[int] = None):
        self._store = store if store is not None else store_lib.SessionStore()
        self._budget = (int(budget_bytes) if budget_bytes is not None
                        else session_lib.resident_byte_budget())
        self._max_inflight = (int(max_inflight) if max_inflight is not None
                              else max_inflight_default())
        self.default_deadline_s = default_deadline_s
        self._lock = threading.Lock()
        self._inflight = 0
        # LRU order: least-recently-queried first.
        self._sessions: "collections.OrderedDict[str, session_lib.DatasetSession]"
        self._sessions = collections.OrderedDict()
        if ops_port is None:
            ops_port = ops_plane_lib.env_ops_port()
        self._ops_server = (ops_plane_lib.serve_ops(self, port=ops_port)
                            if ops_port is not None else None)

    @property
    def store(self) -> store_lib.SessionStore:
        return self._store

    @property
    def budget_bytes(self) -> int:
        return self._budget

    @property
    def max_inflight(self) -> int:
        return self._max_inflight

    @property
    def ops_server(self):
        """The running obs endpoint (ops_plane.OpsServer), or None."""
        return self._ops_server

    # -- membership ------------------------------------------------------

    def create(self, name: str, data, **session_kwargs
               ) -> session_lib.DatasetSession:
        """Ingests a new session and admits it under the fleet budget
        (kwargs go to DatasetSession; ``name`` is forced)."""
        session_kwargs["name"] = name
        session = session_lib.DatasetSession(data, **session_kwargs)
        return self.attach(session)

    def open(self, name: str, **open_kwargs) -> session_lib.DatasetSession:
        """Re-hydrates a stored session from the manager's store and
        admits it."""
        session = self._store.open(name, **open_kwargs)
        return self.attach(session)

    def open_live(self, name: str, **open_kwargs
                  ) -> session_lib.DatasetSession:
        """Reopens a stored LIVE session (append-WAL replay + union
        fold; serving/live.py) from the manager's store and admits it —
        its appends and scheduled releases then run under the fleet's
        admission gate and deadlines like any query."""
        session = self._store.open_live(name, **open_kwargs)
        return self.attach(session)

    def attach(self, session: session_lib.DatasetSession
               ) -> session_lib.DatasetSession:
        """Admits an existing session: it joins the LRU set, its queries
        route through the admission gate and default deadline, and its
        bytes count against the fleet budget (which may demote others
        right now)."""
        with self._lock:
            if session.name in self._sessions:
                raise ValueError(
                    f"a session named {session.name!r} is already "
                    f"admitted")
            session._manager = self
            self._sessions[session.name] = session
        self._enforce_budget(protect=session)
        return session

    def get(self, name: str) -> session_lib.DatasetSession:
        with self._lock:
            if name not in self._sessions:
                raise KeyError(f"no admitted session named {name!r}")
            return self._sessions[name]

    def remove(self, name: str) -> session_lib.DatasetSession:
        """Detaches a session from the fleet (does not close it)."""
        with self._lock:
            session = self._sessions.pop(name)
        session._manager = None
        return session

    def close(self) -> None:
        """Closes every admitted session and empties the fleet (and
        stops the obs endpoint when one is running)."""
        if self._ops_server is not None:
            self._ops_server.close()
            self._ops_server = None
        with self._lock:
            sessions = list(self._sessions.values())
            self._sessions.clear()
        for session in sessions:
            session._manager = None
            session.close()

    # -- queries ---------------------------------------------------------

    def query(self, name: str, params, **query_kwargs):
        """Routes one query to an admitted session (re-hydrating it
        first when spilled); equivalent to ``get(name).query(...)``."""
        return self.get(name).query(params, **query_kwargs)

    def query_batch(self, name: str, configs, **batch_kwargs):
        """Routes a batch through the session's query planner
        (re-hydrating first when spilled); equivalent to
        ``get(name).query_batch(...)``. The whole batch rides one
        admission slot — shedding is all-or-nothing, matching the
        plan's all-or-nothing refund domain."""
        return self.get(name).query_batch(configs, **batch_kwargs)

    @contextlib.contextmanager
    def admission(self):
        """The bounded in-flight gate: entered by every query of a
        managed session. Full gate → typed shed, never a queue.

        The gate-acquisition wait (lock contention — sheds don't wait,
        by design) feeds the ``pipelinedp_tpu_admission_wait_seconds``
        histogram, and the in-flight count is exported as a gauge."""
        t0 = time.perf_counter()
        with self._lock:
            if self._inflight >= self._max_inflight:
                profiler.count_event(EVENT_SHED)
                # Admission decisions feed the flight recorder (via the
                # span-event hook, tracer or not): a post-mortem shows
                # the overload the process was shedding against.
                obs_trace.event("shed", inflight=self._inflight,
                                max_inflight=self._max_inflight)
                raise SessionOverloadedError(self._inflight,
                                             self._max_inflight)
            self._inflight += 1
            obs_metrics.inflight_queries().set(self._inflight)
        obs_metrics.admission_wait_seconds().observe(
            time.perf_counter() - t0)
        try:
            yield
        finally:
            with self._lock:
                self._inflight -= 1
                obs_metrics.inflight_queries().set(self._inflight)

    def notify_used(self, session, rehydrated: bool) -> None:
        """Called by a session at query start (after its lifecycle lock
        dropped): LRU-touch, and re-enforce the budget when the query
        just re-hydrated a spilled session."""
        with self._lock:
            if session.name in self._sessions:
                self._sessions.move_to_end(session.name)
        if rehydrated:
            self._enforce_budget(protect=session)

    # -- the demotion ladder ---------------------------------------------

    def resident_bytes(self) -> int:
        """Fleet-wide resident bytes (device + host slab + bound caches
        of every non-spilled admitted session)."""
        with self._lock:
            sessions = list(self._sessions.values())
        return sum(s.stats()["resident_bytes"] for s in sessions
                   if not s.is_spilled)

    def _enforce_budget(self, protect=None) -> None:
        """Demotes LRU sessions one rung at a time until the fleet fits
        the budget: device copy dropped first, then spill-to-store. The
        ``protect`` session (the one just admitted or re-hydrated) and
        sessions with queries in flight are skipped — at worst the
        fleet transiently overshoots by the active working set, it
        never thrashes the session being served."""
        while True:
            resident = self.resident_bytes()
            obs_metrics.fleet_resident_bytes().set(resident)
            if resident <= self._budget:
                return
            with self._lock:
                candidates = [s for s in self._sessions.values()
                              if s is not protect and not s.is_spilled]
            demoted = False
            for candidate in candidates:  # LRU first
                with obs_trace.span("fleet/demote",
                                    session=candidate.name):
                    if candidate.demote_device():
                        profiler.count_event(EVENT_DEMOTIONS)
                        obs_trace.event("demote_device",
                                        session=candidate.name)
                        demoted = True
                        break
                    if candidate.spill(self._store):
                        profiler.count_event(EVENT_DEMOTIONS)
                        profiler.count_event(EVENT_SPILLS)
                        obs_trace.event("spill", session=candidate.name)
                        demoted = True
                        break
            if not demoted:
                return  # nothing left to demote; overshoot transiently

    # -- observability ---------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            names = list(self._sessions)
            inflight = self._inflight
        per_session = {}
        for name in names:
            try:
                per_session[name] = self.get(name).stats()
            except KeyError:
                continue
        return {
            "budget_bytes": self._budget,
            "resident_bytes": self.resident_bytes(),
            "max_inflight": self._max_inflight,
            "inflight": inflight,
            "default_deadline_s": self.default_deadline_s,
            "ops_url": (self._ops_server.url
                        if self._ops_server is not None else None),
            "sessions": per_session,
        }
