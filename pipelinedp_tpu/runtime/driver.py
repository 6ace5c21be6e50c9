"""The unified resilient slab driver.

Every streaming encode path — the single-device slab pipeline
(ops/streaming.py) and the mesh chunk pipeline (parallel/sharded.py) —
is the same fold: host-encode a window of pid-disjoint chunks, ship it
with one async ``device_put``, fold each chunk into the running
accumulators under its ``fold_in(key, c)`` key, checkpoint at window
boundaries, and recover from faults without changing a single released
bit. Until this module existed that fold lived twice
(``ops/streaming._run_slab_loop`` and
``parallel/sharded._run_codec_chunks``), and every resilience or
scheduling feature — checkpoint/resume, OOM-adaptive retry, lookahead
prefetch, compact merge, fault injection — had to be patched in both.

:class:`SlabDriver` is that loop, written once. Everything
device-topology-specific hides behind a :class:`DevicePlacement`
strategy: how a slab lands on silicon, how a chunk folds, how state is
snapshotted and restored. Two placements exist today (single device,
mesh); a multi-host placement plugs in without a third copy of the
loop.

The driver additionally owns the dispatch watchdog
(runtime/watchdog.py): with a timeout configured, the injector check +
transfer, every chunk dispatch, and one per-window
``block_until_ready`` sync run under a bounded budget, so a wedged
transfer surfaces as a typed, retryable :class:`~pipelinedp_tpu.runtime
.watchdog.DispatchHangError` instead of hanging the loop forever. A
timed-out *step or sync* is treated like an in-dispatch failure: the
abandoned operation may still be mutating donated buffers, so the only
trustworthy state is the last checkpoint (restore, or re-raise when
none exists).

Failure handling (see RESILIENCE.md for the full fault-domain table):

  * ``oom`` — degradable placements halve the slab window and re-issue
    from the failed chunk (chunk keys don't depend on the window
    grouping, so released values are unchanged); non-degradable
    placements (mesh: the chunk granularity is fixed by the mesh shape)
    fall back to counted retries.
  * ``transient`` (injected faults, gRPC-style transient status codes,
    watchdog hangs) — bounded exponential backoff, re-issue.
  * ``fatal`` (HostCrash, privacy guards, everything else) — propagate.

A failure raised while a *donating* chunk step was in flight may have
consumed the donated accumulator buffers; those retries restore from
the last checkpoint and re-raise when no checkpoint exists (resuming
from possibly-poisoned buffers could double-count a chunk).
"""

from __future__ import annotations

import abc
import dataclasses
import time
from typing import Any, Callable, List, Optional, Tuple

from pipelinedp_tpu import profiler
from pipelinedp_tpu.obs import flight as obs_flight
from pipelinedp_tpu.obs import metrics as obs_metrics
from pipelinedp_tpu.obs import trace as obs_trace
from pipelinedp_tpu.runtime import checkpoint as checkpoint_lib
from pipelinedp_tpu.runtime import retry as retry_lib
from pipelinedp_tpu.runtime import watchdog as watchdog_lib

# Profiler event counters owned by the slab loop (profiler.count_event /
# event_count; surfaced by runtime.resilience_counters).
EVENT_RETRIES = "runtime/retries"
EVENT_DEGRADATIONS = "runtime/degradations"
EVENT_RESUMES = "runtime/resumes"
EVENT_CHECKPOINT_BYTES = "runtime/checkpoint_bytes"
# One per DispatchHangError the driver acted on (retried or surfaced);
# the raw per-timeout count is watchdog.EVENT_WATCHDOG_TIMEOUTS.
EVENT_HANGS = "runtime/hangs_detected"

# Per-executed-chunk counters (canonical here; ops/streaming re-exports
# them under the same names for the test suites):
#   EVENT_PARTITION_SCATTERS — full-[num_partitions] scatter passes whose
#     input is row/group scale (one set per chunk on the legacy path);
#   EVENT_COMPACT_CHUNKS — chunks that emitted compact group columns
#     (their merge-time scatters are counted by the merge closures under
#     ops/streaming.EVENT_COMPACT_MERGE_SCATTERS).
EVENT_PARTITION_SCATTERS = "ops/partition_scatter_passes"
EVENT_COMPACT_CHUNKS = "ops/compact_chunk_emits"

# Per-shipped-window wire counters: the bytes of each host slab handed to
# DevicePlacement.transfer (padding included) and the input rows of that
# window's chunks. Counted only by placements that ship host bytes; a
# retried window ships, and counts, again.
EVENT_WIRE_BYTES = "ops/wire_bytes"
EVENT_WIRE_ROWS = "ops/wire_rows"


def count_wire(nbytes: int, rows: int) -> None:
    """Credits one shipped host payload to the wire counters."""
    profiler.count_event(EVENT_WIRE_BYTES, int(nbytes))
    profiler.count_event(EVENT_WIRE_ROWS, int(rows))


class DevicePlacement(abc.ABC):
    """Where slabs land and how chunk results fold, for one topology.

    The driver owns scheduling, retries, checkpoints, prefetch and the
    watchdog; the placement owns everything that touches device state.
    Implementations: ``ops/streaming._SingleDevicePlacement`` and
    ``parallel/sharded._MeshPlacement``. A future multi-host placement
    implements this same interface.

    Class attributes:
      stage_prefix: span name prefix per slab window (the window's first
        chunk index is appended).
      prefetch_prefix: thread-name prefix for the lookahead encode pool.
      degradable: device OOM halves the slab window (single-device);
        False re-issues the window as a counted retry (mesh — chunk
        granularity is fixed by the mesh shape).
      donates: non-compact chunk steps donate the accumulator buffers
        into the kernel, so a failure mid-step poisons them (recovery
        must restore from a checkpoint). Compact steps never donate.
      compact: chunk results are compact per-group columns collected in
        ``pending`` and folded by :meth:`merge_pending` at checkpoints
        and once at the end, instead of dense per-chunk scatters.
      ships_host_bytes: :meth:`transfer` copies the host slab to the
        device, so the driver counts it in ``ops/wire_bytes`` /
        ``ops/wire_rows`` (False where the wire is already on the device
        or nothing lands on one).
    """

    stage_prefix: str = "dp/stream_slab_"
    prefetch_prefix: str = "pdp-slab-prefetch"
    degradable: bool = False
    donates: bool = False
    compact: bool = False
    ships_host_bytes: bool = True

    @abc.abstractmethod
    def init_state(self) -> Tuple[Any, Any]:
        """Initial (accs, qhist) before any chunk folds."""

    @abc.abstractmethod
    def transfer(self, slab, s0: int, s1: int) -> Any:
        """Ships the host slab for window [s0, s1); returns the device
        payload the chunk steps consume."""

    @abc.abstractmethod
    def step(self, c: int, payload, offset: int, accs, qhist
             ) -> Tuple[Any, Any]:
        """Folds chunk ``c`` (``payload`` row ``offset``) into the
        accumulators; returns the new (accs, qhist)."""

    def compact_step(self, c: int, payload, offset: int) -> Any:
        """Compact-mode chunk kernel: returns the chunk's pending
        compact-group columns (only called when ``compact``)."""
        raise NotImplementedError

    def merge_pending(self, accs, pending: List[Any]) -> Any:
        """Folds the pending compact chunks into the dense accumulators
        (only called when ``compact``)."""
        raise NotImplementedError

    @abc.abstractmethod
    def snapshot(self, accs, qhist) -> Tuple[Tuple, Optional[Any]]:
        """Host copies of the accumulator state for a checkpoint."""

    @abc.abstractmethod
    def restore(self, cp: checkpoint_lib.StreamCheckpoint,
                expects_qhist: bool) -> Tuple[Any, Any]:
        """Fresh device state from a validated checkpoint."""

    def sync(self, accs, qhist, pending) -> None:
        """Blocks until the window's dispatched work is materialized —
        the watchdog's per-window progress bound (only called with a
        watchdog attached)."""
        import jax

        state = [x for x in (accs, qhist) if x is not None]
        jax.block_until_ready(state + list(pending))


@dataclasses.dataclass
class SlabPlan:
    """The static schedule of one streamed run.

    fmt_desc is an opaque description of the wire layout (it enters the
    checkpoint wire fingerprint verbatim, so it must be stable across
    the checkpointing and resuming processes). prefetch_depth bounds the
    background host-encode lookahead (0 disables).

    retain_sink, when set, is the driver's retain-wire mode: it is
    called with ``(s0, s1, slab)`` for every successfully prepared host
    slab window, letting a resident-dataset session keep the sorted wire
    chunks instead of discarding them after the fold
    (ops/streaming.ingest_resident_wire; SERVING.md). It must be
    idempotent per ``(s0, s1)`` range — retries, OOM-degraded windows
    and resumes may prepare (and therefore retain) a range more than
    once, and degradations change the window boundaries.

    buckets_per_chunk is how many entries of ``counts`` one chunk spans
    (the mesh ships n_dev codec buckets per chunk); the wire-rows counter
    sums a window's entries.
    """
    n_chunks: int
    window_chunks: int
    fmt_desc: str
    counts: Any
    n_uniq: Optional[Any]
    buckets_per_chunk: int = 1
    scatter_passes: int = 5
    quantile: bool = False
    data_digest_fn: Optional[Callable[[], str]] = None
    prefetch_depth: int = 0
    retain_sink: Optional[Callable[[int, int, Any], None]] = None


class SlabDriver:
    """One resilient pass over a :class:`SlabPlan`'s chunk schedule.

    ``prepare_slab(s0, s1)`` is the pure host encode of window
    [s0, s1) — pure in the sense that a discarded prefetch, a degraded
    window, or a resume may simply call it again (the native per-bucket
    sort is idempotent; released values never depend on scheduling).
    """

    def __init__(self, placement: DevicePlacement, plan: SlabPlan,
                 prepare_slab: Callable[[int, int], Any], key,
                 resilience=None):
        self._placement = placement
        self._plan = plan
        self._prepare_slab = prepare_slab
        self._key = key
        self._resilience = resilience

    def _watchdog(self) -> Optional[watchdog_lib.DispatchWatchdog]:
        timeout = None
        if self._resilience is not None:
            timeout = self._resilience.watchdog_timeout_s
        if timeout is None:
            timeout = watchdog_lib.env_timeout_s()
        return (watchdog_lib.DispatchWatchdog(timeout)
                if timeout is not None else None)

    def run(self) -> Tuple[Any, Any]:
        """Returns the final (accs, qhist); qhist is None unless the
        plan streams quantile histograms."""
        placement, plan = self._placement, self._plan
        resilience = self._resilience
        k = plan.n_chunks
        accs, qhist = placement.init_state()

        policy = injector = cp_policy = deadline = None
        key_fp = wire_fp = None
        cursor = 0
        if resilience is not None:
            policy = resilience.retry_policy
            injector = resilience.fault_injector
            cp_policy = resilience.checkpoint_policy
            deadline = getattr(resilience, "deadline", None)
            if cp_policy is not None or resilience.resume_from is not None:
                key_fp = checkpoint_lib.key_fingerprint(self._key)
                wire_fp = checkpoint_lib.wire_fingerprint(
                    k, plan.fmt_desc, plan.counts, plan.n_uniq,
                    data_digest=(plan.data_digest_fn()
                                 if plan.data_digest_fn else ""))
                cp = resilience.resume_from
                if cp is None and cp_policy is not None:
                    cp = cp_policy.store.load(cp_policy.run_id)
                if cp is not None:
                    cp.validate(key_fp=key_fp, wire_fp=wire_fp, n_chunks=k,
                                key_counter=resilience.key_counter)
                    accs, qhist = placement.restore(
                        cp, expects_qhist=plan.quantile)
                    cursor = int(cp.next_chunk)
                    profiler.count_event(EVENT_RESUMES)
                    obs_trace.event("resume", next_chunk=cursor)

        def save_checkpoint(next_chunk, accs, qhist):
            t0 = time.perf_counter()
            with obs_trace.span("dp/checkpoint",
                                next_chunk=int(next_chunk)):
                host_accs, host_q = placement.snapshot(accs, qhist)
                cp = checkpoint_lib.StreamCheckpoint(
                    run_id=cp_policy.run_id, next_chunk=next_chunk,
                    n_chunks=k, accs=host_accs, qhist=host_q,
                    key_fingerprint=key_fp, wire_fingerprint=wire_fp,
                    key_counter=resilience.key_counter)
                cp_policy.store.save(cp)
            profiler.count_event(EVENT_CHECKPOINT_BYTES, cp.nbytes())
            obs_metrics.checkpoint_write_seconds().observe(
                time.perf_counter() - t0)

        compact = placement.compact
        donating = placement.donates and not compact
        pending = []  # compact mode: per-chunk columns since last merge

        window = max(1, plan.window_chunks)
        ordinal = 0  # window starts incl. re-issues (fault script index)
        failures = 0  # consecutive failed attempts of the current window
        since_checkpoint = 0

        wd = self._watchdog()

        def guarded(what, fn):
            return wd.call(what, fn) if wd is not None else fn()

        # Lookahead prefetch pool: window keys are the exact (s0, s1)
        # ranges, so a budget degradation naturally invalidates stale
        # prefetches; stage times recorded by pool threads merge into
        # this thread's collectors via the adopted sinks.
        depth = plan.prefetch_depth
        executor = None
        inflight = {}
        parent_sinks = obs_trace.current_sinks()
        parent_span = obs_trace.current()
        ships = placement.ships_host_bytes
        per_chunk = max(1, plan.buckets_per_chunk)

        def prefetch_call(a, b):
            with obs_trace.adopt_sinks(parent_sinks), \
                    obs_trace.attach(parent_span):
                with obs_trace.span("dp/wire_sort_parallel", chunk0=a,
                                    chunk1=b):
                    return self._prepare_slab(a, b)

        def discard_inflight():
            for fut in inflight.values():
                fut.cancel()
            inflight.clear()

        try:
            if depth > 0 and k > 1:
                import concurrent.futures
                executor = concurrent.futures.ThreadPoolExecutor(
                    max_workers=depth,
                    thread_name_prefix=placement.prefetch_prefix)
            while cursor < k:
                if deadline is not None:
                    # Cooperative per-query deadline (serving): checked
                    # OUTSIDE the retry handler so an expired query
                    # propagates typed and immediately — it never burns
                    # retries or backoff against an exhausted budget.
                    deadline.check(f"slab window starting at chunk "
                                   f"{cursor}")
                s1 = min(cursor + window, k)
                this_window = ordinal
                ordinal += 1
                in_dispatch = False
                w0, t_w0 = cursor, time.perf_counter()
                try:
                    with obs_trace.span(
                            f"{placement.stage_prefix}{cursor}",
                            chunk0=cursor, chunk1=s1, attempt=failures):
                        fut = inflight.pop((cursor, s1), None)
                        # Main thread until the host slab is ready: the
                        # wait on the prefetch thread, or the inline
                        # encode when nothing was prefetched.
                        with obs_trace.span("dp/slab_wait",
                                            prefetched=fut is not None):
                            slab = (fut.result() if fut is not None
                                    else self._prepare_slab(cursor, s1))
                        if plan.retain_sink is not None:
                            # Retain-wire mode: hand the validated host
                            # slab to the session before it is consumed
                            # (the corruption guard already ran inside
                            # prepare_slab).
                            plan.retain_sink(cursor, s1, slab)
                        if executor is not None:
                            nxt0 = s1
                            while len(inflight) < depth and nxt0 < k:
                                nxt1 = min(nxt0 + window, k)
                                if (nxt0, nxt1) not in inflight:
                                    inflight[(nxt0, nxt1)] = \
                                        executor.submit(prefetch_call,
                                                        nxt0, nxt1)
                                nxt0 = nxt1
                        s0 = cursor

                        def do_transfer():
                            # The injector's transfer-point faults (incl.
                            # the blocking ``hang`` kind) fire inside the
                            # watchdog guard, like the real transfer.
                            if injector is not None:
                                injector.check("transfer", this_window)
                            return placement.transfer(slab, s0, s1)

                        with obs_trace.span("dp/slab_transfer"):
                            payload = guarded(f"transfer of window "
                                              f"[{s0}, {s1})", do_transfer)
                        if ships:
                            count_wire(slab.nbytes, plan.counts[
                                s0 * per_chunk:s1 * per_chunk].sum())
                        if injector is not None:
                            injector.check("kernel", this_window)
                        for c in range(s0, s1):
                            with obs_trace.span("dp/slab_dispatch",
                                                chunk=c):
                                if compact:
                                    pending.append(guarded(
                                        f"chunk {c} dispatch",
                                        lambda c=c: placement.compact_step(
                                            c, payload, c - s0)))
                                    profiler.count_event(
                                        EVENT_COMPACT_CHUNKS)
                                else:
                                    in_dispatch = donating
                                    accs, qhist = guarded(
                                        f"chunk {c} dispatch",
                                        lambda c=c: placement.step(
                                            c, payload, c - s0, accs,
                                            qhist))
                                    in_dispatch = False
                                    profiler.count_event(
                                        EVENT_PARTITION_SCATTERS,
                                        plan.scatter_passes)
                            cursor = c + 1
                        if wd is not None:
                            # The per-window progress bound. A timeout
                            # here means dispatched-but-unmaterialized
                            # state: only a checkpoint is trustworthy.
                            in_dispatch = True
                            with obs_trace.span("dp/slab_sync"):
                                wd.call("window sync",
                                        lambda: placement.sync(accs, qhist,
                                                               pending))
                            in_dispatch = False
                except Exception as exc:
                    failure_kind = retry_lib.classify(exc)
                    if isinstance(exc, watchdog_lib.DispatchHangError):
                        profiler.count_event(EVENT_HANGS)
                        obs_trace.event("watchdog_timeout",
                                        error=type(exc).__name__)
                    if policy is None or failure_kind == retry_lib.FATAL:
                        raise
                    if in_dispatch:
                        # The failing step may have consumed its donated
                        # accumulator buffers (or, after a sync timeout,
                        # the abandoned dispatch may still be mutating
                        # them); only a checkpoint restores trustworthy
                        # state.
                        cp = (cp_policy.store.load(cp_policy.run_id)
                              if cp_policy is not None else None)
                        if cp is None:
                            raise
                        cp.validate(key_fp=key_fp, wire_fp=wire_fp,
                                    n_chunks=k,
                                    key_counter=resilience.key_counter)
                        accs, qhist = placement.restore(
                            cp, expects_qhist=plan.quantile)
                        cursor = int(cp.next_chunk)
                        pending.clear()
                        profiler.count_event(EVENT_RESUMES)
                        obs_trace.event("resume", next_chunk=cursor)
                    if (failure_kind == retry_lib.OOM
                            and placement.degradable):
                        smaller = policy.degrade_slab_buckets(window)
                        if smaller < window:
                            # Re-issue from the failed chunk with a
                            # halved window; the per-chunk key schedule
                            # is untouched, so results are unchanged.
                            # Window boundaries move — in-flight
                            # prefetches for the old boundaries are
                            # discarded (pure recompute).
                            window = smaller
                            discard_inflight()
                            profiler.count_event(EVENT_DEGRADATIONS)
                            obs_trace.event("degrade",
                                            window_chunks=smaller)
                            continue
                    failures += 1
                    if failures > policy.max_retries:
                        raise
                    if deadline is not None:
                        # Never back off past the query's budget.
                        deadline.check(f"retry of window [{cursor}, {s1})")
                    profiler.count_event(EVENT_RETRIES)
                    obs_trace.event("retry", error=type(exc).__name__,
                                    attempt=failures)
                    policy.sleep(policy.backoff_s(failures - 1))
                    continue
                # Window timing into the always-on flight recorder: the
                # post-mortem of a later hang shows how far the stream
                # got and how fast it was moving.
                obs_flight.record(
                    "window", chunk0=w0, chunk1=cursor,
                    ms=round((time.perf_counter() - t_w0) * 1000.0, 3),
                    attempt=failures)
                failures = 0
                since_checkpoint += 1
                if (cp_policy is not None and cursor < k
                        and since_checkpoint >= cp_policy.every_slabs):
                    if compact and pending:
                        # Fold pending compact chunks into the dense base
                        # so the checkpoint format stays dense
                        # accumulators.
                        accs = placement.merge_pending(accs, pending)
                        pending = []
                    save_checkpoint(cursor, accs, qhist)
                    since_checkpoint = 0
        finally:
            discard_inflight()
            if executor is not None:
                executor.shutdown(wait=True)
            if wd is not None:
                wd.close()
        if compact and pending:
            accs = placement.merge_pending(accs, pending)
            pending = []
        if cp_policy is not None and cp_policy.delete_on_success:
            cp_policy.store.delete(cp_policy.run_id)
        return accs, qhist
