"""Profiling hooks: JAX profiler traces with DP-stage annotations.

A capability the reference lacks (SURVEY.md §5 — its only observability is
the explain-computation report): wrap any engine call in
``with profiler.profile("/tmp/trace"):`` and open the result in
TensorBoard/Perfetto; the engine's stages show up as named trace spans.

    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import profiler

    with profiler.profile("/tmp/dp_trace"):
        result = engine.aggregate(data, params)
        accountant.compute_budgets()
        result.to_columns()

Spans are ``obs.trace.span`` (the one span system: profiler annotation,
stage-time sinks and, when installed, the obs tracer); ``stage(name)`` is
its attribute-free delegate, and the sink helpers below are re-exported
from there. Annotations are near-free when no trace is active, so they
stay in the engine permanently.

Thread-safety contract (PR 5's pool + lock machinery, checked by dplint
DPL008): the engine's worker pools (slab prefetch, encode workers) call
into this module from pool threads, so entry points are classified:

  * **pool-safe**: ``stage()``, ``current_sinks()``, ``adopt_sinks()``,
    ``count_event()``, ``event_count()``, ``event_counts()`` — sink
    mutation funnels through ``obs.trace._add_stage_time`` under its
    ``_sink_lock``, counters through the obs metrics registry's single
    lock, and the sink *list* is thread-local (``adopt_sinks`` installs
    the parent's collectors into the worker's own slot, never sharing
    the list object across threads).
  * **owning-thread only**: ``collect_stage_times()`` (registers the
    sink dict on the calling thread; workers must join via
    ``adopt_sinks(current_sinks())`` captured on the parent),
    ``profile()`` / ``reset_events()`` (process-global trace/counter
    state; call from the driver thread, not from workers).

Compile telemetry: importing this module registers one ``jax.monitoring``
listener on ``/jax/core/compile/backend_compile_duration`` (a compile or
a persistent-cache load of one program), which counts ``jit/compiles``
and observes ``pipelinedp_tpu_compile_seconds{program=<jit name>}``.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Iterator, Optional

import jax
import jax.monitoring

from pipelinedp_tpu.obs import metrics as obs_metrics
from pipelinedp_tpu.obs import trace as obs_trace
from pipelinedp_tpu.obs.trace import (  # noqa: F401 — re-exported API
    adopt_sinks, collect_stage_times, current_sinks)

# Global named counters: compile/trace/cache telemetry (ops/finalize uses
# them to count epilogue retraces and executable-cache hits). Unlike stage
# times these are process-global — a retrace is a property of the jit
# caches, which are shared across engines and threads.
#
# Since PR 11 these are back-compat shims over the typed metrics
# registry (pipelinedp_tpu/obs/metrics.py, the "events" namespace):
# every historical caller keeps working, and the same storage feeds the
# Prometheus exposition and JSON snapshot exporters. The registry runs
# every event operation under ONE lock, so reset_events(prefix) racing
# count_event from prefetch/watchdog threads can never lose an
# increment to a detached counter (pinned by the obs hammer tests).


def count_event(name: str, n: int = 1) -> None:
    """Increments a named global counter (e.g. one per jit trace).
    Pool-safe: atomic under the obs metrics-registry lock."""
    obs_metrics.default_registry().event_inc(name, n)


def event_count(name: str) -> int:
    """Current value of a named counter (0 if never incremented)."""
    return obs_metrics.default_registry().event_value(name)


def event_counts() -> Dict[str, int]:
    """Snapshot of all named counters."""
    return obs_metrics.default_registry().event_values()


def reset_events(prefix: Optional[str] = None) -> None:
    """Zeros the named counters (those starting with ``prefix``, or all).

    Test/bench plumbing: counters are process-global, so suites that
    assert on deltas (e.g. the runtime/* resilience counters) reset their
    slice first instead of bookkeeping baselines. Atomic with respect to
    concurrent count_event calls (same registry lock).
    """
    obs_metrics.default_registry().reset_events(prefix)


@contextlib.contextmanager
def profile(logdir: str,
            create_perfetto_link: bool = False) -> Iterator[None]:
    """Captures a JAX profiler trace of the enclosed block into logdir."""
    jax.profiler.start_trace(logdir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def stage(name: str):
    """Names the enclosed host block (and its dispatched device work) in
    the trace and, inside collect_stage_times(), times it: an
    ``obs.trace.span`` without attributes."""
    return obs_trace.span(name)


# The compile listener (module docstring). Registered once per process.
EVENT_COMPILES = "jit/compiles"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_compile(event: str, duration_s: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    count_event(EVENT_COMPILES)
    obs_metrics.compile_seconds().observe(
        duration_s, program=str(kwargs.get("fun_name", "")))


jax.monitoring.register_event_duration_secs_listener(_on_compile)
