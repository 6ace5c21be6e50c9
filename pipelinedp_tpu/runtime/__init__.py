"""Resilient streaming runtime: the unified slab driver, checkpoint/
resume, OOM-adaptive retry, dispatch watchdog, and durable at-most-once
DP release.

The reference inherits fault tolerance from its backends (Beam and Spark
re-execute lost work transparently); the TPU-native runtime gets the
equivalent here, built on two properties the streamed execution path
already has:

  * determinism — per-chunk PRNG keys are ``fold_in(key, c)`` and the
    host encode is a pure function of the input, so re-running any chunk
    reproduces it bitwise;
  * mergeability — ``PartitionAccumulators`` (and the quantile leaf
    histogram) add across pid-disjoint chunks, so a prefix of the chunk
    sequence is a complete, resumable intermediate state.

What lives where:

  * :mod:`driver` — ``SlabDriver``: THE slab loop, written once, driving
    both streaming entry points (single-device ``ops/streaming`` and
    mesh ``parallel/sharded``) through a ``DevicePlacement`` strategy;
    checkpointing, retry, prefetch, compact merge, fault injection and
    the watchdog each exist exactly once here.
  * :mod:`checkpoint` — ``StreamCheckpoint`` snapshots
    ``(accs, qhist, next_chunk, wire/rng fingerprints, KeyStream
    counter)`` after each slab into a ``CheckpointStore`` (in-memory or
    file-backed with payload digests + keep-last-K retention); a resumed
    run is bit-identical to an uninterrupted one.
  * :mod:`retry` — ``RetryPolicy``: bounded exponential backoff for
    transient transfer/kernel errors and watchdog hangs; on
    ``RESOURCE_EXHAUSTED`` the slab byte budget is halved and the failed
    slab re-issued (the per-chunk key schedule never changes, so results
    stay distribution-identical — bit-identical for a seeded run).
  * :mod:`watchdog` — ``DispatchWatchdog``: bounded timeouts around the
    transfer/dispatch/sync points so a wedged device operation surfaces
    as a typed, retryable ``DispatchHangError`` instead of hanging the
    loop forever.
  * :mod:`journal` — ``ReleaseJournal`` / ``FileReleaseJournal``:
    at-most-once noise release, in-memory or durable (fsync'd WAL with
    per-record digests, torn-tail-tolerant recovery, atomic compaction)
    so even a re-exec'd process refuses to re-draw released noise (the
    budget side lives in ``budget_accounting`` as the spend journal,
    durable through the same WAL via ``durable_spend_journal=``).
  * :mod:`faults` — ``FaultInjector``: scripted OOM / transfer / kernel /
    hang / host-crash / SIGKILL faults at slab N, driving
    ``tests/resilience_test.py`` and the cross-process kill harness.

``JaxDPEngine`` exposes all of it via the ``checkpoint_policy=``,
``retry_policy=``, ``release_journal=``, ``fault_injector=`` and
``watchdog_timeout_s=`` knobs; ``ops/streaming.stream_bound_and_aggregate``
and the mesh twin take a ``resilience=`` bundle plus an explicit
``resume_from=`` hook. See RESILIENCE.md for the failure model and
recovery semantics.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from pipelinedp_tpu import profiler
from pipelinedp_tpu.runtime.checkpoint import (  # noqa: F401
    CheckpointMismatchError, CheckpointPolicy, CheckpointStore,
    FileCheckpointStore, InMemoryCheckpointStore, StreamCheckpoint,
    key_fingerprint, wire_fingerprint)
from pipelinedp_tpu.runtime.faults import (  # noqa: F401
    FaultInjector, FaultSpec, HostCrash, InjectedFault, InjectedKernelError,
    InjectedOom, InjectedTransferError)
from pipelinedp_tpu.runtime.journal import (  # noqa: F401
    EVENT_JOURNAL_BYTES, EVENT_JOURNAL_RECOVERIES, DoubleReleaseError,
    FileReleaseJournal, JournalCorruptError, JsonlWal, ReleaseJournal,
    ReleaseRecord)
from pipelinedp_tpu.runtime.retry import RetryPolicy, classify  # noqa: F401
from pipelinedp_tpu.runtime.watchdog import (  # noqa: F401
    EVENT_WATCHDOG_TIMEOUTS, Deadline, DispatchHangError, DispatchWatchdog,
    QueryDeadlineError)
from pipelinedp_tpu.runtime.driver import (  # noqa: F401
    EVENT_CHECKPOINT_BYTES, EVENT_DEGRADATIONS, EVENT_HANGS, EVENT_RESUMES,
    EVENT_RETRIES, DevicePlacement, SlabDriver, SlabPlan)

# Profiler event-counter names (profiler.count_event / event_count).
# Loop-owned counters live in runtime/driver.py, watchdog/journal
# counters in their modules; the native-fallback counter is credited by
# ops/streaming._pack_native.
EVENT_NATIVE_FALLBACK = "runtime/native_fallback"


@dataclasses.dataclass
class StreamResilience:
    """The resilience bundle the streaming drivers consume.

    ``key_counter`` is the engine KeyStream position the streamed kernel
    key was drawn at; checkpoints record it so a resume under a different
    key schedule (which could never be bit-identical) is refused instead
    of silently diverging. -1 = unknown (direct streaming-API callers).

    ``watchdog_timeout_s`` bounds every device transfer/dispatch and adds
    one per-window sync: a wedged operation surfaces as a retryable
    ``DispatchHangError`` within the timeout instead of hanging forever.
    None defers to ``PIPELINEDP_TPU_WATCHDOG_S`` (0 = disabled, the
    default — enabling it trades a little cross-window pipelining for
    bounded hang detection).

    ``deadline`` is the serving layer's per-query time budget
    (watchdog.Deadline): the driver checks it between windows and
    before backoff sleeps and raises ``QueryDeadlineError`` — outside
    the retry handler, so an expired query propagates immediately.
    """
    retry_policy: RetryPolicy = dataclasses.field(default_factory=RetryPolicy)
    fault_injector: Optional[FaultInjector] = None
    checkpoint_policy: Optional[CheckpointPolicy] = None
    resume_from: Optional[StreamCheckpoint] = None
    key_counter: int = -1
    watchdog_timeout_s: Optional[float] = None
    deadline: Optional[Deadline] = None


def resilience_counters() -> Dict[str, int]:
    """Snapshot of the runtime's resilience counters (the benchmark and
    chip_smoke.py report it; all keys always present so dashboards can
    rely on them)."""
    return {
        "retries": profiler.event_count(EVENT_RETRIES),
        "degradations": profiler.event_count(EVENT_DEGRADATIONS),
        "resumes": profiler.event_count(EVENT_RESUMES),
        "checkpoint_bytes": profiler.event_count(EVENT_CHECKPOINT_BYTES),
        "native_fallbacks": profiler.event_count(EVENT_NATIVE_FALLBACK),
        "watchdog_timeouts": profiler.event_count(EVENT_WATCHDOG_TIMEOUTS),
        "hangs_detected": profiler.event_count(EVENT_HANGS),
        "journal_recoveries": profiler.event_count(EVENT_JOURNAL_RECOVERIES),
        "journal_bytes": profiler.event_count(EVENT_JOURNAL_BYTES),
    }
