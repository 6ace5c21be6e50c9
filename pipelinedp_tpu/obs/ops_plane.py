"""The operational plane: live HTTP endpoints over a running fleet.

PR-11 built the telemetry substrate (tracer, typed metrics registry,
audit trail); this module *serves* it, so an operator can ask a live
process "are you healthy, what is p99, which tenant is burning budget"
without attaching a debugger. One stdlib-only
:class:`http.server.ThreadingHTTPServer` (no new dependencies, safe in
any container) exposes:

  * ``GET /metrics``  — the process metric registry in Prometheus text
    exposition 0.0.4 (scrape it directly).
  * ``GET /healthz``  — typed readiness JSON: sessions resident vs
    spilled, watchdog/hang counters, WAL-directory writability, flight
    recorder state. HTTP 200 when healthy, 503 when a hard check (WAL
    writable) fails.
  * ``GET /statusz``  — the fleet snapshot JSON: per-session residency
    tier + inflight work, shed/deadline counters, bound-cache hit rate,
    and the per-tenant ε/δ spent-vs-ledger burn-down. Budgets are
    public quantities; released values (and of course raw data) never
    appear — the serving leak scan covers this surface dynamically.
  * ``GET /debug/flightz`` — the most recent flight-recorder events
    (obs/flight.py), newest last.
  * ``GET /fleetz``   — the failover plane (serving/fleet.py): lease
    holder + fencing token per session, follower replication lag, and
    the process-wide takeover/fence/hedge counters. Lease metadata is
    operational (pid/host/token), never data.

Start it with :func:`serve_ops(manager_or_session, port)` — any object
with a ``stats()`` dict works; ``SessionManager`` and ``DatasetSession``
are the intended targets — or let ``PIPELINEDP_TPU_OPS_PORT`` start it
automatically when a ``SessionManager`` is constructed. ``port=0``
binds an ephemeral port (``server.port`` reports it). The server runs
on daemon threads and holds no locks while rendering: it reads the
same snapshot APIs as any other reader, so a wedged query cannot wedge the
diagnostics that would explain it, and the plane being up or down
cannot change a single released bit (pinned by
tests/obs_serving_test.py).
"""

from __future__ import annotations

import json
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Tuple

from pipelinedp_tpu.obs import flight as flight_lib
from pipelinedp_tpu.obs import metrics as metrics_lib

OPS_PORT_ENV = "PIPELINEDP_TPU_OPS_PORT"

# How many flight events /debug/flightz returns (newest last).
FLIGHTZ_EVENTS = 256


def env_ops_port() -> Optional[int]:
    """Validated PIPELINEDP_TPU_OPS_PORT (None when 0/unset)."""
    from pipelinedp_tpu.native import loader
    port = loader.env_int(OPS_PORT_ENV, 0, 0, 65535)
    return port if port > 0 else None


# -- payload builders (shared with tests and the kill harness) ---------------


def _is_manager(target) -> bool:
    return hasattr(target, "max_inflight") and hasattr(target, "store")


def _residency_tier(session_stats: dict) -> str:
    if session_stats.get("spilled"):
        return "spilled"
    if session_stats.get("wire_device_bytes", 0) > 0:
        return "device"
    return "host"


def _session_statusz(session_stats: dict) -> dict:
    tenants = {}
    for tid, t in (session_stats.get("tenants") or {}).items():
        spent = float(t.get("spent_epsilon", 0.0))
        total = float(t.get("total_epsilon",
                            spent + float(t.get("remaining_epsilon", 0.0))))
        tenants[tid] = dict(
            t,
            total_epsilon=total,
            epsilon_burn_pct=(round(100.0 * spent / total, 2)
                              if total > 0 else 0.0))
    out = {
        "residency": _residency_tier(session_stats),
        "resident_bytes": session_stats.get("resident_bytes", 0),
        "wire_host_bytes": session_stats.get("wire_host_bytes", 0),
        "wire_device_bytes": session_stats.get("wire_device_bytes", 0),
        "bound_cache_bytes": session_stats.get("bound_cache_bytes", 0),
        "bound_cache_entries": session_stats.get("bound_cache_entries", 0),
        "queries": session_stats.get("queries", 0),
        "active_queries": session_stats.get("active_queries", 0),
        "n_chunks": session_stats.get("n_chunks", 0),
        "store": session_stats.get("store"),
        "tenants": tenants,
    }
    if "live" in session_stats:
        out["live"] = session_stats["live"]
    if "planner" in session_stats:
        out["planner"] = session_stats["planner"]
    if session_stats.get("read_only"):
        out["read_only"] = True
    if session_stats.get("fleet"):
        out["fleet"] = session_stats["fleet"]
    return out


def _fleet_counters() -> dict:
    ev = metrics_lib.default_registry().event_values()
    hits = ev.get("serving/bound_cache_hits", 0)
    misses = ev.get("serving/bound_cache_misses", 0)
    return {
        "queries": ev.get("serving/queries", 0),
        "queries_shed": ev.get("serving/queries_shed", 0),
        "query_deadline_hits": ev.get("serving/query_deadline_hits", 0),
        "bound_cache_hits": hits,
        "bound_cache_misses": misses,
        "bound_cache_hit_rate": (round(hits / (hits + misses), 4)
                                 if hits + misses else None),
        "device_fallbacks": ev.get("serving/device_fallbacks", 0),
        "rehydrations": ev.get("serving/sessions_rehydrations", 0),
        "demotions": ev.get("serving/sessions_demotions", 0),
        "spills": ev.get("serving/sessions_spills", 0),
        "watchdog_timeouts": ev.get("runtime/watchdog_timeouts", 0),
        "hangs_detected": ev.get("runtime/hangs_detected", 0),
        "retries": ev.get("runtime/retries", 0),
        "audit_records": ev.get("obs/audit_records", 0),
    }


def statusz_payload(target) -> dict:
    """The /statusz JSON: fleet shape, counters, per-session residency
    and per-tenant budget burn-down. Operational aggregates and public
    budget quantities only — never values, keys, or ids."""
    out = {
        "process_id": os.getpid(),
        "kind": "manager" if _is_manager(target) else "session",
        "counters": _fleet_counters(),
        "flight_events_recorded": flight_lib.recorder().watermark(),
    }
    stats = target.stats()
    if _is_manager(target):
        out.update({
            "budget_bytes": stats.get("budget_bytes"),
            "resident_bytes": stats.get("resident_bytes"),
            "inflight": stats.get("inflight"),
            "max_inflight": stats.get("max_inflight"),
            "default_deadline_s": stats.get("default_deadline_s"),
            "sessions": {name: _session_statusz(s)
                         for name, s in stats.get("sessions", {}).items()},
        })
    else:
        name = getattr(target, "name", "session")
        out["sessions"] = {name: _session_statusz(stats)}
    return out


def _writable(path: Optional[str]) -> Optional[bool]:
    if not path:
        return None
    try:
        probe = os.path.join(path, f".ops_probe_{os.getpid()}")
        with open(probe, "w") as f:
            f.write("ok")
        os.unlink(probe)
        return True
    except OSError:
        return False


def healthz_payload(target) -> Tuple[dict, bool]:
    """The /healthz JSON plus overall readiness. Hard failure: the WAL
    directory (session store root / flight spool dir) is not writable —
    a fleet that cannot persist releases must not take traffic."""
    stats = target.stats()
    if _is_manager(target):
        sessions = stats.get("sessions", {})
        store_root = getattr(target.store, "root", None)
    else:
        sessions = {getattr(target, "name", "session"): stats}
        binding = getattr(target, "store_binding", None)
        store_root = getattr(binding[0], "root", None) if binding else None
    ev = metrics_lib.default_registry().event_values()
    recorder = flight_lib.recorder()
    wal_writable = _writable(store_root)
    spool_dir = (os.path.dirname(recorder.spool_path)
                 if recorder.spool_path else None)
    spool_writable = _writable(spool_dir)
    checks = {
        "sessions_resident": sum(1 for s in sessions.values()
                                 if not s.get("spilled")),
        "sessions_spilled": sum(1 for s in sessions.values()
                                if s.get("spilled")),
        "inflight": stats.get("inflight", stats.get("active_queries", 0)),
        "watchdog": {
            "timeouts": ev.get("runtime/watchdog_timeouts", 0),
            "hangs_detected": ev.get("runtime/hangs_detected", 0),
            "query_deadline_hits": ev.get("serving/query_deadline_hits", 0),
        },
        "wal_writable": wal_writable,
        "flight_recorder": {
            "events": recorder.watermark(),
            "spool": recorder.spool_path,
            "spool_writable": spool_writable,
        },
    }
    ok = wal_writable is not False and spool_writable is not False
    return {"status": "ok" if ok else "unavailable",
            "checks": checks}, ok


def fleetz_payload(target) -> dict:
    """The /fleetz JSON: lease holder, fencing token, follower
    replication lag, and the process-wide failover counters. ``target``
    may be a SessionManager/DatasetSession (``stats()``) or a
    FollowerSession/FleetRouter (``statusz()``)."""
    from pipelinedp_tpu.serving import fleet as fleet_lib
    out = {
        "process_id": os.getpid(),
        "counters": fleet_lib.fleet_counters(),
    }
    statusz = getattr(target, "statusz", None)
    if callable(statusz):  # FollowerSession / FleetRouter
        out["target"] = statusz()
        return out
    stats = target.stats()
    if _is_manager(target):
        per_session = stats.get("sessions", {})
    else:
        per_session = {getattr(target, "name", "session"): stats}
    out["sessions"] = {
        name: {"fleet": s.get("fleet"),
               "read_only": bool(s.get("read_only", False))}
        for name, s in per_session.items()}
    return out


def flightz_payload(last: int = FLIGHTZ_EVENTS) -> dict:
    return {
        "process_id": os.getpid(),
        "spool": flight_lib.recorder().spool_path,
        "events": [e.to_payload()
                   for e in flight_lib.recorder().events(last=last)],
    }


# -- the server --------------------------------------------------------------


class _OpsHandler(BaseHTTPRequestHandler):
    server_version = "pdp-ops/1"

    def log_message(self, fmt, *args):  # keep serving stdout clean
        pass

    def _send(self, code: int, body: bytes, content_type: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _send_json(self, code: int, payload: dict) -> None:
        self._send(code, json.dumps(payload, indent=1).encode(),
                   "application/json")

    def do_GET(self) -> None:  # noqa: N802 (http.server API)
        target = self.server.ops_target  # type: ignore[attr-defined]
        path = self.path.split("?", 1)[0]
        try:
            if path == "/metrics":
                text = metrics_lib.default_registry().to_prometheus()
                self._send(200, text.encode(),
                           "text/plain; version=0.0.4; charset=utf-8")
            elif path == "/healthz":
                payload, ok = healthz_payload(target)
                self._send_json(200 if ok else 503, payload)
            elif path == "/statusz":
                self._send_json(200, statusz_payload(target))
            elif path == "/debug/flightz":
                self._send_json(200, flightz_payload())
            elif path == "/fleetz":
                self._send_json(200, fleetz_payload(target))
            else:
                self._send_json(404, {"error": "unknown endpoint", "endpoints": [
                    "/metrics", "/healthz", "/statusz", "/debug/flightz",
                    "/fleetz"]})
        except BrokenPipeError:
            pass
        except Exception as exc:  # diagnostics must not kill the server
            try:
                self._send_json(500, {"error": f"{type(exc).__name__}: {exc}"})
            except OSError:
                pass


class OpsServer:
    """A running operational-plane endpoint (module docstring).
    Construct via :func:`serve_ops`; ``close()`` stops it."""

    def __init__(self, target, port: int = 0, host: str = "127.0.0.1"):
        self._httpd = ThreadingHTTPServer((host, port), _OpsHandler)
        self._httpd.daemon_threads = True
        self._httpd.ops_target = target  # type: ignore[attr-defined]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name=f"pdp-ops-{self._httpd.server_address[1]}", daemon=True)
        self._thread.start()

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def url(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"http://{host}:{port}"

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=10)

    def __enter__(self) -> "OpsServer":
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> None:
        self.close()


def serve_ops(target, port: Optional[int] = None,
              host: str = "127.0.0.1") -> OpsServer:
    """Starts the observability endpoint over ``target`` (a
    SessionManager or DatasetSession). ``port=None`` consults
    ``PIPELINEDP_TPU_OPS_PORT`` and falls back to an ephemeral port;
    pass an explicit 0 for ephemeral regardless of the env."""
    if port is None:
        port = env_ops_port() or 0
    return OpsServer(target, port=port, host=host)
