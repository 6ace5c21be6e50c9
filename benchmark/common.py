"""What every driver shares: seeds, units of work, and the release a unit
of work produced, read through the public API."""

from __future__ import annotations

import dataclasses
import sys
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from benchmark import reference

# Released column of each metric in ``LazyJaxResult.to_columns()``.
COLUMN = {"COUNT": "count", "SUM": "sum",
          "PRIVACY_ID_COUNT": "privacy_id_count"}


def resilience_counters() -> Dict[str, int]:
    """The program's counters of quiet recovery: retries, degradations,
    resumes, native fallbacks, watchdog timeouts, hangs and serving
    device fallbacks. One that moves marks the unit of work failed."""
    from pipelinedp_tpu import profiler, runtime
    from pipelinedp_tpu.serving import session as session_lib

    c = runtime.resilience_counters()
    out = {k: c[k] for k in ("retries", "degradations", "resumes",
                              "native_fallbacks", "watchdog_timeouts",
                              "hangs_detected")}
    out["device_fallbacks"] = profiler.event_count(
        session_lib.EVENT_DEVICE_FALLBACKS)
    return out


class Seeds:
    """Every seed of a run, derived from ``--seed`` (any non-negative
    integer) so that distinct tags never share a stream."""

    def __init__(self, seed: int):
        if seed < 0:
            raise ValueError("--seed must be non-negative")
        self.seed = seed

    def _state(self, tag: int, i: int) -> int:
        ss = np.random.SeedSequence([self.seed, tag, i + 2])
        return int(ss.generate_state(1)[0]) & 0x7FFFFFFF

    def engine(self, i: int) -> int:
        """Kernel seed of the i-th unit of work (i = -1: warm-up)."""
        return self._state(1, i)


@dataclasses.dataclass
class Item:
    """One aggregate of the window. Times are seconds from the window's
    start."""
    work: int
    start: float = 0.0
    end: float = 0.0
    ok: bool = False
    error: Optional[str] = None
    stages: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Window:
    items: List[Item] = dataclasses.field(default_factory=list)
    releases: List[Tuple[dict, reference.Release]] = dataclasses.field(
        default_factory=list)
    seconds: float = 0.0


def aggregate_params(q: dict):
    import pipelinedp_tpu as pdp
    return pdp.AggregateParams(
        metrics=[getattr(pdp.Metrics, m) for m in q["metrics"]],
        noise_kind=getattr(pdp.NoiseKind, q["noise_kind"]),
        max_partitions_contributed=q["max_partitions_contributed"],
        max_contributions_per_partition=q[
            "max_contributions_per_partition"],
        min_value=q["min_value"], max_value=q["max_value"])


def to_release(result, q: dict) -> reference.Release:
    """The kept partition keys and, per metric, their released values."""
    cols = result.to_columns()
    keep = np.asarray(cols["keep_mask"])
    keys = np.asarray(result.partition_keys())
    return reference.Release(
        keys=keys,
        values={m: np.asarray(cols[COLUMN[m]])[keep] for m in q["metrics"]})


def release_of_aggregate(data, q: dict, seed: int) -> reference.Release:
    """One cold batch release: a fresh accountant and engine."""
    import pipelinedp_tpu as pdp
    accountant = pdp.NaiveBudgetAccountant(q["epsilon"], q["delta"])
    engine = pdp.JaxDPEngine(accountant, seed=seed)
    result = engine.aggregate(data, aggregate_params(q))
    accountant.compute_budgets()
    return to_release(result, q)


def run_item(item: Item, window: Window, t0: float, span: str,
             fn: Callable[[], Tuple[dict, reference.Release]]) -> None:
    """Runs one unit of work and records it with the program's stage
    times. An exception, or a resilience counter that moved, marks it
    failed."""
    import jax
    from pipelinedp_tpu import profiler

    before = resilience_counters()
    item.start = time.perf_counter() - t0
    try:
        with jax.profiler.TraceAnnotation(span), \
                profiler.collect_stage_times() as stages:
            q, rel = fn()
        item.stages = dict(stages)
        item.ok = True
    except Exception as exc:  # a failed unit is counted, not fatal
        item.error = f"{type(exc).__name__}: {exc}"
        traceback.print_exc(file=sys.stderr)
    item.end = time.perf_counter() - t0
    moved = {k: v - before[k] for k, v in resilience_counters().items()
             if v != before[k]}
    if item.ok and moved:
        item.ok = False
        item.error = f"resilience counters moved: {moved}"
    window.items.append(item)
    if item.ok:
        window.releases.append((q, rel))
