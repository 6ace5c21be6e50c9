"""Fleet observability: span tracing, typed metrics, DP release audit.

The serving fleet's answer to "why was this query slow", "what is p99
admission wait", and "exactly which DP releases has tenant X been
charged for" (OBSERVABILITY.md):

  * :mod:`~pipelinedp_tpu.obs.trace` — the program's one span call,
    ``trace.span(name, **attrs)``: a profiler annotation on the device
    trace's clock, the stage-time sinks, and (when a
    :class:`~pipelinedp_tpu.obs.trace.Tracer` is installed) a
    parent-linked span, threaded through ``DatasetSession.query`` /
    ``query_batch``, the ``SessionManager`` gate, the engine's encode,
    wire preparation and finalize, and the ``runtime.SlabDriver``
    windows (wait / transfer / dispatch / sync, retry / degrade /
    watchdog events); exports Chrome trace-event JSON
    (Perfetto-loadable) per query or per process.
    Knob: ``PIPELINEDP_TPU_TRACE``.
  * :mod:`~pipelinedp_tpu.obs.metrics` — a typed registry (counters,
    gauges, fixed-bucket latency histograms) with Prometheus text
    exposition and a JSON snapshot API; absorbs the legacy
    ``profiler.count_event`` namespace behind back-compat shims.
    Knob: ``PIPELINEDP_TPU_METRICS``.
  * :mod:`~pipelinedp_tpu.obs.audit` — an append-only, per-tenant
    release audit trail on the runtime's fsync'd WAL machinery:
    mechanism kinds, (ε, δ) charged, kept/dropped partition counts,
    timings, typed outcomes; survives SIGKILL on store-bound sessions.

The operational plane (PR 13) serves and persists all of it:

  * :mod:`~pipelinedp_tpu.obs.flight` — the always-on bounded
    flight recorder (post-mortem ring buffer + spool + slow-query
    captures). Knobs: ``PIPELINEDP_TPU_FLIGHT_DIR``,
    ``PIPELINEDP_TPU_SLOW_QUERY_S``, ``PIPELINEDP_TPU_CAPTURE_DIR``.
  * :mod:`~pipelinedp_tpu.obs.ops_plane` — stdlib HTTP endpoints over
    a live fleet: ``/metrics``, ``/healthz``, ``/statusz``,
    ``/debug/flightz``. Knob: ``PIPELINEDP_TPU_OPS_PORT``.

DP-safety is a hard API rule, not a convention: raw pids, partition
keys, and unreleased (pre-noise) values never enter any obs record —
span attributes, metric labels and audit fields are validated scalars
(``TelemetryLeakError`` otherwise), and dplint rule DPL011
(telemetry-taint) flags offending flows statically.

Instrumented code must never be able to change released bits: tracing
reads clocks and counters, never data or keys, and results are pinned
bit-identical with tracing on or off (tests/obs_serving_test.py).
"""

from pipelinedp_tpu.obs import flight, metrics, ops_plane, trace  # noqa: F401
from pipelinedp_tpu.obs.flight import (  # noqa: F401
    CAPTURE_DIR_ENV, FLIGHT_DIR_ENV, SLOW_QUERY_ENV, FlightEvent,
    FlightRecorder)
from pipelinedp_tpu.obs.metrics import (  # noqa: F401
    METRICS_ENV, Counter, Gauge, Histogram, MetricsRegistry,
    TelemetryLeakError, check_safe_value, default_registry)
from pipelinedp_tpu.obs.ops_plane import (  # noqa: F401
    OPS_PORT_ENV, OpsServer, serve_ops)
from pipelinedp_tpu.obs.trace import TRACE_ENV, Span, Tracer  # noqa: F401

# obs.audit imports runtime.journal (which imports the profiler); load
# it lazily so `import pipelinedp_tpu.profiler` -> obs never cycles.
_LAZY = {"audit", "AuditRecord", "AuditTrail", "AuditCorruptError",
         "OUTCOMES"}


def __getattr__(name: str):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module("pipelinedp_tpu.obs.audit")
        return mod if name == "audit" else getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
