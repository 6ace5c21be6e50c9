"""Typed metrics registry: counters, gauges, fixed-bucket histograms.

The fleet's quantitative telemetry lives here (OBSERVABILITY.md "Metric
catalog"). Three typed instrument families plus the legacy *event*
namespace that absorbed ``profiler.count_event``:

  * :class:`Counter` — monotonically increasing, optionally labeled.
  * :class:`Gauge` — last-write-wins point-in-time value (fleet resident
    bytes, in-flight queries).
  * :class:`Histogram` — fixed cumulative buckets + sum + count, the
    shape Prometheus quantile queries (``histogram_quantile``) consume.
    The default bucket ladder spans 100µs..120s, the serving latency
    range.
  * events — the flat ``profiler.count_event`` counter namespace
    (``runtime/retries``, ``serving/queries``, ...). ``profiler``'s
    ``count_event`` / ``event_count`` / ``event_counts`` /
    ``reset_events`` are back-compat shims over this registry, so
    ``runtime.resilience_counters()`` and ``serving.fleet_counters()``
    read the same storage exporters scrape.

Exports: :meth:`MetricsRegistry.to_prometheus` (text exposition format)
and :meth:`MetricsRegistry.snapshot` (a JSON-able dict).
``PIPELINEDP_TPU_METRICS=<path>`` writes the exposition
there at process exit (a ``.json`` suffix writes the snapshot instead).

Atomicity contract (the PR-11 counter-hygiene fix): every registry
operation — increments, gauge sets, histogram observations, reads, and
``reset_events(prefix)`` — runs under ONE registry lock, so a
``reset_events`` racing ``count_event`` from prefetch or watchdog
threads can never lose an increment to a detached family (the hammer
tests in tests/obs_test.py pin this).

DP-safety: instruments carry *operational* aggregates — timings,
counts of queries/retries/evictions — never raw pids, partition keys,
or pre-noise values. Label values are validated scalars; arrays are
refused outright. dplint DPL011 statically flags private columns
flowing into any ``obs.*`` API.

This module is deliberately dependency-free (stdlib only): it imports
neither jax nor any pipelinedp_tpu module, so the profiler shim and the
runtime can use it without import cycles.
"""

from __future__ import annotations

import atexit
import bisect
import math
import os
import re
import tempfile
import threading
from typing import Dict, List, Optional, Sequence, Tuple

METRICS_ENV = "PIPELINEDP_TPU_METRICS"

# Cumulative upper bounds (seconds) for latency histograms: 100µs..120s
# covers everything from a bound-cache hit to a cold mesh ingest.
DEFAULT_LATENCY_BUCKETS_S: Tuple[float, ...] = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)

_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")
_LABEL_RE = re.compile(r"[^a-zA-Z0-9_]")

# Attribute/label keys that smell like raw private data. The hard rule
# (OBSERVABILITY.md "DP-safety stance"): raw pids, partition keys and
# unreleased (pre-noise) values never enter any obs record. Shared with
# obs.trace and obs.audit.
FORBIDDEN_KEYS = frozenset({
    "pid", "pids", "privacy_id", "privacy_ids", "pk", "pks",
    "partition_key", "partition_keys", "value", "values", "raw_values",
    "accs", "acc", "accumulators", "qhist",
})


class TelemetryLeakError(ValueError):
    """A private-data-shaped payload was about to enter an obs record."""


def check_safe_value(key: str, value) -> None:
    """The shared obs-record payload gate: refuses forbidden key names
    and non-scalar values (an array or sequence reaching telemetry is
    row-level data by construction — aggregate it or drop it)."""
    if key in FORBIDDEN_KEYS:
        raise TelemetryLeakError(
            f"obs record key {key!r} names a raw private column; "
            f"telemetry must carry operational aggregates only "
            f"(OBSERVABILITY.md DP-safety stance)")
    if value is None or isinstance(value, (bool, int, float, str)):
        return
    raise TelemetryLeakError(
        f"obs record key {key!r} carries a non-scalar {type(value).__name__}; "
        f"arrays and sequences never enter telemetry records")


def sanitize_name(name: str) -> str:
    """A legal Prometheus metric name for an arbitrary event name."""
    out = _NAME_RE.sub("_", name)
    if out and out[0].isdigit():
        out = "_" + out
    return out


def _label_key(labels: Dict[str, str]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _fmt_labels(pairs: Tuple[Tuple[str, str], ...],
                extra: Optional[Tuple[str, str]] = None) -> str:
    items = list(pairs)
    if extra is not None:
        items.append(extra)
    if not items:
        return ""
    body = ",".join(
        '%s="%s"' % (_LABEL_RE.sub("_", k),
                     v.replace("\\", "\\\\").replace('"', '\\"'))
        for k, v in items)
    return "{" + body + "}"


def _fmt_value(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)


class _Instrument:
    """Base: one named family of labeled series, locked by the registry."""

    kind = "untyped"

    def __init__(self, name: str, help_text: str, lock: threading.RLock):
        self.name = name
        self.help = help_text
        self._lock = lock
        self._series: Dict[Tuple[Tuple[str, str], ...], object] = {}

    def _check_labels(self, labels: Dict[str, str]) -> None:
        for k, v in labels.items():
            check_safe_value(k, v)

    def series(self) -> dict:
        """Snapshot {label-string: value} of every series."""
        with self._lock:
            return {json_label(k): self._series_value(v)
                    for k, v in self._series.items()}

    def _series_value(self, raw):
        return raw


def json_label(pairs: Tuple[Tuple[str, str], ...]) -> str:
    return ",".join(f"{k}={v}" for k, v in pairs) if pairs else ""


class Counter(_Instrument):
    kind = "counter"

    def inc(self, n: float = 1, **labels) -> None:
        self._check_labels(labels)
        key = _label_key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0) + n

    def value(self, **labels) -> float:
        with self._lock:
            return self._series.get(_label_key(labels), 0)


class Gauge(_Instrument):
    kind = "gauge"

    def set(self, v: float, **labels) -> None:
        self._check_labels(labels)
        check_safe_value("gauge_value", v)
        with self._lock:
            self._series[_label_key(labels)] = v

    def inc(self, n: float = 1, **labels) -> None:
        self._check_labels(labels)
        key = _label_key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0) + n

    def dec(self, n: float = 1, **labels) -> None:
        self.inc(-n, **labels)

    def value(self, **labels) -> float:
        with self._lock:
            return self._series.get(_label_key(labels), 0)


class _HistSeries:
    __slots__ = ("counts", "sum", "count")

    def __init__(self, n_buckets: int):
        self.counts = [0] * n_buckets  # per-bucket (non-cumulative)
        self.sum = 0.0
        self.count = 0


class Histogram(_Instrument):
    """Fixed-bucket histogram; buckets are cumulative upper bounds in
    the exposition (``le``), stored non-cumulative internally."""

    kind = "histogram"

    def __init__(self, name: str, help_text: str, lock: threading.RLock,
                 buckets: Sequence[float] = DEFAULT_LATENCY_BUCKETS_S):
        super().__init__(name, help_text, lock)
        bounds = sorted(float(b) for b in buckets)
        if not bounds:
            raise ValueError(f"histogram {name!r} needs at least 1 bucket")
        self.buckets: Tuple[float, ...] = tuple(bounds)

    def observe(self, v: float, **labels) -> None:
        self._check_labels(labels)
        check_safe_value("observation", v)
        v = float(v)
        key = _label_key(labels)
        with self._lock:
            series = self._series.get(key)
            if series is None:
                series = self._series[key] = _HistSeries(
                    len(self.buckets) + 1)
            # bisect_left: bucket bound is inclusive (le semantics).
            series.counts[bisect.bisect_left(self.buckets, v)] += 1
            series.sum += v
            series.count += 1

    def snapshot(self, **labels) -> dict:
        """{buckets, counts (cumulative), sum, count} of one series."""
        with self._lock:
            series = self._series.get(_label_key(labels))
            counts = (list(series.counts) if series is not None
                      else [0] * (len(self.buckets) + 1))
            cumulative, acc = [], 0
            for c in counts:
                acc += c
                cumulative.append(acc)
            return {
                "buckets": list(self.buckets) + [math.inf],
                "counts": cumulative,
                "sum": series.sum if series is not None else 0.0,
                "count": series.count if series is not None else 0,
            }

    def _series_value(self, raw: _HistSeries):
        cumulative, acc = [], 0
        for c in raw.counts:
            acc += c
            cumulative.append(acc)
        return {"counts": cumulative, "sum": raw.sum, "count": raw.count}


class MetricsRegistry:
    """The process metric store (module docstring). One lock guards
    every family and the event namespace, making reset-vs-increment
    races impossible by construction."""

    def __init__(self):
        self._lock = threading.RLock()
        self._families: Dict[str, _Instrument] = {}
        self._events: Dict[str, int] = {}

    # -- typed families ---------------------------------------------------

    def _family(self, cls, name: str, help_text: str, **kwargs):
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = self._families[name] = cls(name, help_text,
                                                 self._lock, **kwargs)
            elif not isinstance(fam, cls):
                raise ValueError(
                    f"metric {name!r} already registered as {fam.kind}")
            return fam

    def counter(self, name: str, help_text: str = "") -> Counter:
        return self._family(Counter, name, help_text)

    def gauge(self, name: str, help_text: str = "") -> Gauge:
        return self._family(Gauge, name, help_text)

    def histogram(self, name: str, help_text: str = "",
                  buckets: Sequence[float] = DEFAULT_LATENCY_BUCKETS_S
                  ) -> Histogram:
        return self._family(Histogram, name, help_text, buckets=buckets)

    # -- the legacy event namespace (profiler.count_event shims) ----------

    def event_inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._events[name] = self._events.get(name, 0) + n

    def event_value(self, name: str) -> int:
        with self._lock:
            return self._events.get(name, 0)

    def event_values(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._events)

    def reset_events(self, prefix: Optional[str] = None) -> None:
        """Zeros event counters (those starting with ``prefix``, or
        all) — atomic with respect to concurrent ``event_inc``: both
        run under the registry lock, so an increment lands either
        before the reset (and is cleared) or after (and survives),
        never in a detached family."""
        with self._lock:
            if prefix is None:
                self._events.clear()
            else:
                for name in [n for n in self._events
                             if n.startswith(prefix)]:
                    del self._events[name]

    # -- exports ----------------------------------------------------------

    def snapshot(self) -> dict:
        """JSON-able dump of everything."""
        with self._lock:
            families = {}
            for name, fam in self._families.items():
                families[name] = {"kind": fam.kind, "series": fam.series()}
                if isinstance(fam, Histogram):
                    families[name]["buckets"] = (list(fam.buckets)
                                                 + ["+Inf"])
            return {"events": dict(self._events), "families": families}

    def to_prometheus(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        with self._lock:
            lines: List[str] = []
            for name in sorted(self._families):
                fam = self._families[name]
                pname = sanitize_name(name)
                if fam.kind == "counter" and not pname.endswith("_total"):
                    pname += "_total"
                if fam.help:
                    lines.append(f"# HELP {pname} {fam.help}")
                lines.append(f"# TYPE {pname} {fam.kind}")
                for key, raw in sorted(fam._series.items()):
                    if isinstance(fam, Histogram):
                        acc = 0
                        for bound, c in zip(
                                list(fam.buckets) + [math.inf],
                                raw.counts):
                            acc += c
                            lines.append(
                                f"{pname}_bucket"
                                f"{_fmt_labels(key, ('le', _fmt_value(bound)))}"
                                f" {acc}")
                        lines.append(
                            f"{pname}_sum{_fmt_labels(key)}"
                            f" {_fmt_value(raw.sum)}")
                        lines.append(
                            f"{pname}_count{_fmt_labels(key)} {raw.count}")
                    else:
                        lines.append(
                            f"{pname}{_fmt_labels(key)} {_fmt_value(raw)}")
            if self._events:
                lines.append("# HELP pipelinedp_tpu_events_total Legacy "
                             "profiler.count_event counters.")
                lines.append("# TYPE pipelinedp_tpu_events_total counter")
                for name in sorted(self._events):
                    lines.append(
                        "pipelinedp_tpu_events_total"
                        f"{_fmt_labels((), ('event', name))}"
                        f" {self._events[name]}")
            return "\n".join(lines) + "\n"

    def reset(self) -> None:
        """Full reset (tests only): families and events."""
        with self._lock:
            self._families.clear()
            self._events.clear()


_default: Optional[MetricsRegistry] = None
_default_lock = threading.Lock()


def default_registry() -> MetricsRegistry:
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = MetricsRegistry()
    return _default


# -- the serving metric catalog (OBSERVABILITY.md) ---------------------------
#
# Central constructors so every call site shares one family (names,
# types, label sets live here and in the doc's catalog table).

def query_seconds() -> Histogram:
    """End-to-end serving query latency, labeled by outcome
    (released / refunded / shed / deadline-expired /
    double-release-refused)."""
    return default_registry().histogram(
        "pipelinedp_tpu_query_seconds",
        "End-to-end DatasetSession.query latency by outcome.")


def admission_wait_seconds() -> Histogram:
    """Time a query spent acquiring the fleet admission gate."""
    return default_registry().histogram(
        "pipelinedp_tpu_admission_wait_seconds",
        "Admission-gate acquisition wait per query.")


def replay_seconds() -> Histogram:
    """Resident-wire replay (chunk kernels) per bound-cache miss."""
    return default_registry().histogram(
        "pipelinedp_tpu_replay_seconds",
        "Resident-wire kernel replay latency per bound-cache miss.")


def finalize_seconds() -> Histogram:
    """The fused DP finalize epilogue (selection + noise + transfer)."""
    return default_registry().histogram(
        "pipelinedp_tpu_finalize_seconds",
        "Fused finalize epilogue latency per aggregate.")


def checkpoint_write_seconds() -> Histogram:
    """One checkpoint snapshot+persist in the slab driver."""
    return default_registry().histogram(
        "pipelinedp_tpu_checkpoint_write_seconds",
        "Slab-driver checkpoint snapshot+persist latency.")


def rehydration_seconds() -> Histogram:
    """Spilled-session re-hydration (store load + wire reload)."""
    return default_registry().histogram(
        "pipelinedp_tpu_rehydration_seconds",
        "Spilled-session re-hydration latency.")


def append_seconds() -> Histogram:
    """One live-session append end to end (digest + micro-encode + WAL
    commit + epoch fold), labeled by outcome (committed / duplicate /
    shed / late-rejected / dead-lettered / failed)."""
    return default_registry().histogram(
        "pipelinedp_tpu_append_seconds",
        "LiveDatasetSession.append latency by outcome.")


def release_tick_seconds() -> Histogram:
    """One scheduled continual-release window (ReleaseSchedule), labeled
    by outcome (released / recovered / suppressed)."""
    return default_registry().histogram(
        "pipelinedp_tpu_release_tick_seconds",
        "Scheduled continual-release window latency by outcome.")


# Compile (or persistent-cache load) seconds of one program: from a cache
# hit of a small epilogue to a cold mesh chunk program (~630 s).
COMPILE_BUCKETS_S: Tuple[float, ...] = (
    0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0, 600.0)


def compile_seconds() -> Histogram:
    """One XLA compile or persistent-cache load, labeled by program (the
    jit function's name); fed by the profiler's jax.monitoring
    listener, which also counts the ``jit/compiles`` event."""
    return default_registry().histogram(
        "pipelinedp_tpu_compile_seconds",
        "XLA compile or compile-cache load latency by program.",
        buckets=COMPILE_BUCKETS_S)


def fleet_resident_bytes() -> Gauge:
    """Fleet-wide resident bytes across admitted sessions."""
    return default_registry().gauge(
        "pipelinedp_tpu_fleet_resident_bytes",
        "Resident bytes across all non-spilled admitted sessions.")


def inflight_queries() -> Gauge:
    """Queries currently inside the admission gate."""
    return default_registry().gauge(
        "pipelinedp_tpu_inflight_queries",
        "Queries currently executing under the admission gate.")


# -- PIPELINEDP_TPU_METRICS exit export --------------------------------------

_exit_registered = False


def _export_at_exit(path: str) -> None:
    reg = default_registry()
    data = (reg.to_prometheus() if not path.endswith(".json")
            else __import__("json").dumps(reg.snapshot(), indent=1))
    try:
        # Atomic publish: scrapers polling the textfile never see a
        # half-written export, even if the process dies mid-dump.
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise
    except OSError:
        pass  # exit-time export is best-effort by design


def _maybe_register_exit_export() -> None:
    global _exit_registered
    path = os.environ.get(METRICS_ENV, "")
    if path and not _exit_registered:
        _exit_registered = True
        atexit.register(_export_at_exit, path)


_maybe_register_exit_export()
