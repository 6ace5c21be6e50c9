"""LiveDatasetSession: crash-exactly-once streaming append with windowed
continual DP releases (SERVING.md "Live sessions").

A batch DatasetSession freezes its dataset at ingest. A live session
accepts **appends** — micro-batches of new rows — while staying durable
and queryable, under three contracts:

  * **Crash-exactly-once append.** Each micro-batch commits through a
    write-ahead discipline: the raw rows land durably first (atomic npz
    under ``epochs/``), then one fsync'd append-WAL record carrying the
    batch's content digest — and *that WAL append is the commit point*.
    SIGKILL at any instant leaves the reopened session
    (``SessionStore.open_live``) at exactly epoch N or N+1, never a torn
    in-between, and re-submitting a batch whose digest the WAL already
    carries is an idempotent no-op — the producer may retry blindly.
  * **Bit-identity to cold.** The fold is a deterministic re-encode of
    the union of committed rows through the very ingest pipeline a cold
    ``DatasetSession`` runs (same pinned chunk count, same mesh bucket
    layout), so every query of the live session — full or windowed — is
    bit-identical to the same query over the same rows ingested cold.
    Appending per-epoch slabs instead would split privacy units across
    buckets (pid-disjoint bucketing is what the chunk kernels' DP
    bounding relies on); the union re-encode keeps the invariant by
    construction, at O(total rows) per append.
  * **At-most-once releases.** Windowed releases ride the existing
    release-token journal: a :class:`ReleaseSchedule` answers each
    sealed window exactly once across restarts — a crash between the
    release and its outcome record is recovered as ``"recovered"``
    (the token refuses to re-draw; the charge is refunded), and a
    deliberate replay of a recorded window surfaces
    ``DoubleReleaseError``.

Event time is the **epoch axis**: each append carries an integer
``event_epoch`` (default: one past the largest seen). The watermark is
driven by the data (plus explicit :meth:`~LiveDatasetSession.
advance_watermark` calls); a batch older than
``watermark - allowed_lateness`` is *late* and is either rejected with a
typed :class:`LateArrivalError` or persisted to the dead-letter
directory — the operator's choice (``WindowSpec.late_policy``). A
window ``[a, b)`` is **sealed** once no acceptable future event can land
in it; only sealed windows are queryable/releasable, which is what makes
their answers deterministic.

Backpressure mirrors query admission: more than ``max_pending_appends``
concurrent appends shed with a typed :class:`IngestOverloadedError`
*before* any durable or budget effect, so a shed append needs no undo.

Constraints (all checked): live sessions are store-bound from birth,
need ``public_partitions`` (the vocabulary must not grow with the data)
and an explicit ``n_chunks`` (the pinned schedule is what makes reopen
deterministic), take numeric columns only (epoch payloads are
``allow_pickle=False`` npz), and skip source verification — each epoch
carries its own content digest instead.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import signal
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from pipelinedp_tpu import profiler
from pipelinedp_tpu.obs import flight as obs_flight
from pipelinedp_tpu.obs import metrics as obs_metrics
from pipelinedp_tpu.obs import trace as obs_trace
from pipelinedp_tpu.ops import encoding, streaming
from pipelinedp_tpu.runtime import journal as journal_lib
from pipelinedp_tpu.serving.session import DatasetSession

# Tuning knobs (validated via native.loader.env_int; README "Tuning
# knobs" + SERVING.md):
#   PIPELINEDP_TPU_MAX_PENDING_APPENDS — concurrent appends admitted
#     before the ingest gate sheds (default 64). The constructor's
#     max_pending_appends= overrides, including an explicit 0 (shed
#     everything — the backpressure tests use it).
MAX_PENDING_ENV = "PIPELINEDP_TPU_MAX_PENDING_APPENDS"
#   PIPELINEDP_TPU_APPEND_COMMIT_WINDOW_MS — bounded group-commit
#     window (default 0): the fsync leader waits this long so racing
#     appends ride one fsync. 0 still group-commits opportunistically
#     (appends that land while a leader is fsyncing coalesce behind the
#     next leader); >0 trades append latency for fewer fsyncs.
APPEND_COMMIT_WINDOW_ENV = "PIPELINEDP_TPU_APPEND_COMMIT_WINDOW_MS"
# Test seam for the kill harness (tests/kill_harness.py): "<stage>" or
# "<stage>@<n>" SIGKILLs the process at that append/release stage —
# "encode" fires before the WAL record is written (reopen lands at
# epoch N), "commit" after the record is written+flushed but before the
# group fsync (the page cache survives SIGKILL, so reopen lands at N+1;
# only power loss could tear it), "fold" after the fsync barrier
# (reopen lands at N+1), "release" between a scheduled window's release
# and its outcome record (catch-up recovers it).
LIVE_CRASH_ENV = "PIPELINEDP_TPU_LIVE_CRASH"

# Profiler event counters (profiler.count_event / event_count):
EVENT_APPENDS = "serving/appends"
EVENT_APPEND_DUPLICATES = "serving/append_duplicates"
EVENT_APPENDS_SHED = "serving/appends_shed"
EVENT_LATE_REJECTED = "serving/late_arrivals_rejected"
EVENT_LATE_DEADLETTERED = "serving/late_arrivals_deadlettered"
EVENT_EPOCH_FOLDS = "serving/epoch_folds"
EVENT_SCHEDULED_RELEASES = "serving/scheduled_releases"
EVENT_RELEASES_RECOVERED = "serving/scheduled_releases_recovered"
EVENT_RELEASES_SUPPRESSED = "serving/scheduled_releases_suppressed"
# Appends refused by the single-writer fence (serving/fleet.py): a
# superseded ex-primary tried to write; the batch is dead-lettered so
# the data is quarantined, never folded under a stale lease.
EVENT_APPENDS_FENCED = "serving/appends_fenced"


def max_pending_appends_default() -> int:
    """Validated PIPELINEDP_TPU_MAX_PENDING_APPENDS (default 64)."""
    from pipelinedp_tpu.native import loader
    return loader.env_int(MAX_PENDING_ENV, 64, 1, 1 << 16)


def append_commit_window_s() -> float:
    """Validated PIPELINEDP_TPU_APPEND_COMMIT_WINDOW_MS as seconds
    (default 0: opportunistic coalescing only)."""
    from pipelinedp_tpu.native import loader
    return loader.env_int(APPEND_COMMIT_WINDOW_ENV, 0, 0, 1000) / 1000.0


def live_counters() -> Dict[str, int]:
    """Snapshot of the live-session counters."""
    return {
        "appends": profiler.event_count(EVENT_APPENDS),
        "append_duplicates": profiler.event_count(EVENT_APPEND_DUPLICATES),
        "appends_shed": profiler.event_count(EVENT_APPENDS_SHED),
        "late_arrivals_rejected": profiler.event_count(EVENT_LATE_REJECTED),
        "late_arrivals_deadlettered": profiler.event_count(
            EVENT_LATE_DEADLETTERED),
        "epoch_folds": profiler.event_count(EVENT_EPOCH_FOLDS),
        "scheduled_releases": profiler.event_count(
            EVENT_SCHEDULED_RELEASES),
        "scheduled_releases_recovered": profiler.event_count(
            EVENT_RELEASES_RECOVERED),
        "scheduled_releases_suppressed": profiler.event_count(
            EVENT_RELEASES_SUPPRESSED),
        "appends_fenced": profiler.event_count(EVENT_APPENDS_FENCED),
    }


def _maybe_crash(stage: str, ordinal: int) -> None:
    """The kill-harness seam (LIVE_CRASH_ENV): a real SIGKILL — no
    cleanup, no atexit — at a named stage, optionally only at the
    given append-epoch / window-start ordinal."""
    spec = os.environ.get(LIVE_CRASH_ENV, "")
    if not spec:
        return
    want_stage, _, want_n = spec.partition("@")
    if want_stage != stage:
        return
    if want_n and int(want_n) != ordinal:
        return
    os.kill(os.getpid(), signal.SIGKILL)


class LateArrivalError(RuntimeError):
    """A batch arrived behind the watermark's lateness allowance under
    the "reject" policy: accepting it would mutate windows that may
    already be sealed (and released)."""

    def __init__(self, event_epoch: int, horizon: int):
        super().__init__(
            f"late arrival: event_epoch={event_epoch} is behind the "
            f"lateness horizon {horizon} (watermark minus "
            f"allowed_lateness); the batch was refused — route it to a "
            f"dead-letter flow or configure late_policy='dead_letter'")
        self.event_epoch = event_epoch
        self.horizon = horizon


class IngestOverloadedError(RuntimeError):
    """The append gate is full: this batch is shed, not queued — before
    any durable or budget effect, so retrying it later is safe (and
    idempotent even if a racing duplicate did commit)."""

    def __init__(self, pending: int, max_pending: int):
        super().__init__(
            f"live ingest overloaded: {pending} appends pending (gate "
            f"{max_pending}); batch shed — retry with backoff")
        self.pending = pending
        self.max_pending = max_pending


@dataclasses.dataclass(frozen=True)
class WindowSpec:
    """Windowing over the epoch axis.

    size: window length in event epochs; windows are half-open
      ``[a, a + size)``.
    slide: start-to-start distance — ``None`` (tumbling, slide == size)
      or any positive int (sliding; overlapping when < size).
    allowed_lateness: how far behind the largest seen event an append
      may land before it is *late*. A window ``[a, b)`` is sealed once
      ``b <= max_event - allowed_lateness`` — no acceptable future
      event can reach it.
    late_policy: "reject" (typed LateArrivalError) or "dead_letter"
      (the batch persists under the store's dead-letter directory and
      a counter ticks; it never folds).
    """
    size: int
    slide: Optional[int] = None
    allowed_lateness: int = 0
    late_policy: str = "reject"

    def __post_init__(self):
        if self.size < 1:
            raise ValueError(f"window size must be >= 1, got {self.size}")
        if self.slide is not None and self.slide < 1:
            raise ValueError(f"slide must be >= 1, got {self.slide}")
        if self.allowed_lateness < 0:
            raise ValueError(
                f"allowed_lateness must be >= 0, got "
                f"{self.allowed_lateness}")
        if self.late_policy not in ("reject", "dead_letter"):
            raise ValueError(
                f"late_policy must be 'reject' or 'dead_letter', got "
                f"{self.late_policy!r}")

    @property
    def stride(self) -> int:
        return self.slide if self.slide is not None else self.size

    def windows_sealed_by(self, horizon: int) -> List[tuple]:
        """All ``[a, b)`` windows with ``b <= horizon``, in order."""
        out = []
        a = 0
        while a + self.size <= horizon:
            out.append((a, a + self.size))
            a += self.stride
        return out

    def to_meta(self) -> dict:
        return {"size": self.size, "slide": self.slide,
                "allowed_lateness": self.allowed_lateness,
                "late_policy": self.late_policy}

    @classmethod
    def from_meta(cls, meta: dict) -> "WindowSpec":
        return cls(size=meta["size"], slide=meta["slide"],
                   allowed_lateness=meta["allowed_lateness"],
                   late_policy=meta["late_policy"])


@dataclasses.dataclass(frozen=True)
class AppendResult:
    """One append's outcome. ``committed`` is True only when the batch
    became a new epoch; duplicates and dead-lettered batches report
    their identity without mutating the fold."""
    epoch: int  # the committed epoch index (or the duplicate's)
    digest: str
    n_rows: int
    event_epoch: int
    committed: bool
    duplicate: bool = False
    dead_lettered: bool = False


@dataclasses.dataclass
class _LiveBinding:
    """The private contract with DatasetSession.query(_live=...): the
    window's resident-dataset view plus the ledger window tag its
    charge carries (per-window budget caps)."""
    view: Any
    window_tag: Optional[str]


class _WindowView:
    """A sealed window as a resident dataset: duck-types exactly what
    JaxDPEngine._aggregate touches (pk_vocab, n_rows,
    _check_engine_compat, _accumulate) and routes the replay through
    the owning session's wire-parameterized accumulate path — so
    window queries share the bound cache, deadline handoff, and
    OOM-degradation machinery of full-session queries."""

    is_resident_dataset = True

    def __init__(self, session: "LiveDatasetSession", wire, a: int,
                 b: int):
        self._session = session
        self._wire = wire
        self._bounds = (a, b)

    @property
    def pk_vocab(self):
        return self._session.pk_vocab

    @property
    def n_rows(self) -> int:
        # The engine derives contribution caps from n_rows: it must be
        # the WINDOW's row count for cold-parity, not the session's.
        return self._wire.n_rows

    @property
    def num_partitions(self) -> int:
        return self._wire.num_partitions

    @property
    def n_chunks(self) -> int:
        # The pinned schedule, not wire.n_chunks: an empty window's
        # wire has zero buckets but the cold-parity engine still wants
        # the session's chunk count.
        return self._session.live_n_chunks

    def _check_engine_compat(self, engine, public_partitions) -> None:
        self._session._check_engine_compat(engine, public_partitions)

    def _accumulate(self, k_kernel, *, mesh, resilience=None, **kw):
        a, b = self._bounds
        return self._session._accumulate_wire(
            self._wire, ("window", a, b, self._wire.fingerprint),
            k_kernel, mesh=mesh, resilience=resilience, **kw)


class LiveDatasetSession(DatasetSession):
    """A DatasetSession that grows by appends (module docstring).

    Create with :meth:`create` (store-bound from birth); reopen after
    process death with ``SessionStore.open_live`` — never the batch
    ``open``, which refuses live sessions because their authoritative
    state is the append WAL plus epoch payloads, not the wire spill.
    """

    @classmethod
    def create(cls, *, store, name: str,
               public_partitions: Sequence[Any],
               n_chunks: int,
               window: WindowSpec,
               mesh=None,
               resident_bytes: Optional[int] = None,
               secure_host_noise: bool = True,
               segment_sort="auto",
               compact_merge="auto",
               epilogue_cache=None,
               max_pending_appends: Optional[int] = None
               ) -> "LiveDatasetSession":
        """An empty live session, durably registered in ``store`` before
        it returns (epoch 0 exists on disk the instant create does)."""
        if public_partitions is None:
            raise ValueError(
                "live sessions need public_partitions: the partition "
                "vocabulary is fixed at creation — a vocabulary that "
                "grew with appended data would leak which partitions "
                "arrived")
        if n_chunks is None or int(n_chunks) < 1:
            raise ValueError(
                "live sessions need an explicit n_chunks >= 1: the "
                "pinned chunk schedule is what makes every fold and "
                "reopen bit-deterministic")
        if store is None:
            raise ValueError(
                "live sessions are store-bound from birth (the append "
                "WAL and epoch payloads live under the store); pass "
                "store=")
        vocab = encoding.Vocabulary(list(public_partitions))
        n_dev = mesh.devices.size if mesh is not None else 1
        self = cls._restore(
            dataclasses.replace(
                streaming._empty_resident_wire(max(len(vocab), 1)),
                n_dev=n_dev),
            vocab,
            public_partitions=public_partitions, mesh=mesh, name=name,
            secure_host_noise=secure_host_noise,
            segment_sort=segment_sort, compact_merge=compact_merge,
            resident_bytes=resident_bytes, epilogue_cache=epilogue_cache,
            store_binding=None)
        self._init_live(window, int(n_chunks), max_pending_appends)
        # Single-writer from birth: the lease is taken BEFORE any
        # durable state exists, so a concurrent create/open of the same
        # name is refused instead of interleaved (serving/fleet.py).
        lease = store._acquire_lease(name, None, False)
        try:
            # Durable birth: wire spill + manifest, then the live
            # section — register_tenant and open_live both need the
            # manifest to exist.
            self._store_binding = (store, name)
            self.save(store)
            store.record_live(name, self._live_meta())
            self._wal = journal_lib.JsonlWal(store.append_wal_path(name))
            self._attach_lease(lease)
        except BaseException:
            lease.release()
            raise
        return self

    def _init_live(self, window: WindowSpec, n_chunks: int,
                   max_pending_appends: Optional[int]) -> None:
        self._live_window = window
        self._live_n_chunks = n_chunks
        self._max_pending = (int(max_pending_appends)
                             if max_pending_appends is not None
                             else max_pending_appends_default())
        self._append_lock = threading.Lock()
        self._pending = 0
        self._pending_lock = threading.Lock()
        # One dict per committed epoch, in commit order: {"epoch",
        # "digest", "n_rows", "event_epoch"}; rows retained raw for the
        # union fold and window views.
        self._epochs: List[dict] = []
        self._epoch_rows: Dict[int, tuple] = {}
        self._digests: Dict[str, int] = {}  # content digest -> epoch
        self._deadletters: set = set()
        self._max_event = -1
        self._has_value: Optional[bool] = None
        self._window_wires: Dict[tuple, Any] = {}
        self._wal: Optional[journal_lib.JsonlWal] = None
        # Group-commit state (SERVING.md "The append commit point"):
        # epochs are assigned and WAL-written under _append_lock, then
        # *staged* until the group fsync covers their WAL ticket; only
        # then do they promote (in epoch order) into _epochs. The fold
        # coalesces: one union re-encode may cover several promotions.
        self._next_epoch = 0
        self._staged: Dict[int, dict] = {}        # epoch -> staged rec
        self._staged_digests: Dict[str, dict] = {}  # digest -> same rec
        self._fold_lock = threading.Lock()
        self._folded_epochs = 0
        # Replication cursor (serving/fleet.py FollowerSession): how
        # many append-WAL records this session's state reflects —
        # recovered count on a writable reopen, poll-applied count on a
        # read-only follower.
        self._applied_wal_records = 0

    # -- identity & status ------------------------------------------------

    @property
    def epoch(self) -> int:
        """Committed epoch count — the append-WAL's append-record count."""
        return len(self._epochs)

    @property
    def watermark(self) -> int:
        """One past the largest event epoch seen (0 while empty)."""
        return self._max_event + 1

    @property
    def sealed_horizon(self) -> int:
        """Windows ending at or before this are sealed: no acceptable
        future event can land in them."""
        return self._max_event - self._live_window.allowed_lateness

    @property
    def window_spec(self) -> WindowSpec:
        return self._live_window

    @property
    def live_n_chunks(self) -> int:
        """The pinned per-fold chunk schedule (explicit at create)."""
        return self._live_n_chunks

    def sealed_windows(self) -> List[tuple]:
        """All currently sealed ``[a, b)`` windows, in order."""
        return self._live_window.windows_sealed_by(self.sealed_horizon)

    def is_sealed(self, a: int, b: int) -> bool:
        return b <= self.sealed_horizon

    def live_status(self) -> dict:
        """The live plane of :meth:`stats` — epoch, watermark, window
        configuration, gate pressure (ops_plane /statusz surfaces it)."""
        with self._lock:
            return {
                "epoch": len(self._epochs),
                "max_event": self._max_event,
                "watermark": self._max_event + 1,
                "sealed_horizon": (self._max_event
                                   - self._live_window.allowed_lateness),
                "sealed_windows": len(self.sealed_windows()),
                "window": self._live_window.to_meta(),
                "n_chunks": self._live_n_chunks,
                "pending_appends": self._pending,
                "max_pending_appends": self._max_pending,
                "deadletters": len(self._deadletters),
                "wire_fingerprint": self._wire.fingerprint,
                "role": ("follower" if self._read_only else "primary"),
                "applied_wal_records": self._applied_wal_records,
            }

    def stats(self) -> dict:
        out = super().stats()
        out["live"] = self.live_status()
        return out

    def _live_meta(self) -> dict:
        return {"window": self._live_window.to_meta(),
                "n_chunks": self._live_n_chunks}

    # -- fleet tier (serving/fleet.py) ------------------------------------

    @property
    def applied_wal_records(self) -> int:
        """How many append-WAL records this session's state reflects —
        the follower's replication cursor."""
        with self._lock:
            return self._applied_wal_records

    def _attach_lease(self, lease) -> None:
        """Live sessions don't just hold the lease — they FENCE every
        WAL with it: the append WAL and each tenant's release/ledger
        journals re-check the on-disk lease per append and embed the
        fencing token in the record, so a superseded writer is refused
        at the journal (StaleWriterError), not merely raced."""
        super()._attach_lease(lease)
        fence = lease.admit
        if self._wal is not None:
            self._wal.attach_fence(fence)
        with self._lock:
            tenant_states = list(self._tenants.values())
        for state in tenant_states:
            self._fence_tenant(state, fence)

    @staticmethod
    def _fence_tenant(state, fence) -> None:
        for journal in (state.release_journal, state.ledger._wal):
            if hasattr(journal, "attach_fence"):
                journal.attach_fence(fence)

    def register_tenant(self, *args, **kwargs):
        state = super().register_tenant(*args, **kwargs)
        fence = self._wal_fence()
        if fence is not None:
            self._fence_tenant(state, fence)
        return state

    def apply_wal_payloads(self, payloads) -> None:
        """Folds already-committed append-WAL payloads into a READ-ONLY
        replica (FollowerSession.poll). Each "append" record's epoch
        payload is loaded digest-validated against the record; the
        replica's wire refolds once per batch of records. Refuses on a
        writable session — the primary's own append path owns its
        state."""
        if not self._read_only:
            raise RuntimeError(
                "apply_wal_payloads is the follower replication path; "
                "a writable session folds through append()")
        store, name = self._store_binding
        applied = 0
        for payload in payloads:
            self._apply_wal_payload(payload, store, name)
            applied += 1
        if applied == 0:
            return
        with self._lock:
            self._applied_wal_records += applied
            self._next_epoch = len(self._epochs)
        self._deadletters = set(store.deadletter_digests(name))
        old_fp = self._wire.fingerprint
        new_wire = self._fold_union()
        with self._lock:
            self._wire = new_wire
            self._folded_epochs = len(self._epochs)
            self._sweep_stale_bound_entries(old_fp)
        if (self._mesh is None and new_wire.n_rows > 0
                and new_wire.host_nbytes <= self._byte_budget):
            new_wire.ensure_device()

    def _apply_wal_payload(self, payload: dict, store, name) -> None:
        """Applies one append-WAL record to the in-memory epoch maps
        (shared by the writable _reopen replay and the follower poll;
        the caller refolds the wire afterwards)."""
        kind = payload.get("kind")
        if kind == "advance":
            with self._lock:
                self._max_event = max(self._max_event,
                                      int(payload["event_epoch"]))
            return
        if kind != "append":
            raise journal_lib.JournalCorruptError(
                f"session {name!r}: append-WAL record "
                f"{payload.get('seq')} has unknown kind {kind!r}")
        epoch = int(payload["epoch"])
        digest = payload["content_digest"]
        pid, pk, value = store.load_epoch(name, epoch, digest)
        with self._lock:
            self._epochs.append({
                "epoch": epoch, "digest": digest,
                "n_rows": int(payload["n_rows"]),
                "event_epoch": int(payload["event_epoch"])})
            self._epoch_rows[epoch] = (pid, pk, value)
            self._digests[digest] = epoch
            self._max_event = max(self._max_event,
                                  int(payload["event_epoch"]))
            if self._has_value is None:
                self._has_value = value is not None

    # -- append: the crash-exactly-once transaction -----------------------

    def append(self, pid, pk, value=None, *,
               event_epoch: Optional[int] = None) -> AppendResult:
        """Appends one micro-batch as the next epoch (module docstring
        for the commit discipline). Returns an :class:`AppendResult`;
        re-submitting a committed batch (same content digest) is an
        idempotent no-op reporting ``duplicate=True``.

        ``event_epoch`` places the batch on the window axis (default:
        one past the largest seen — strictly in-order arrival). A batch
        behind ``watermark - allowed_lateness`` follows the late
        policy; an empty batch is refused (advance the watermark with
        :meth:`advance_watermark` instead — an empty append has no
        digest identity to make idempotent).
        """
        self._ensure_writable("append()")
        with self._pending_lock:
            if self._pending >= self._max_pending:
                profiler.count_event(EVENT_APPENDS_SHED)
                obs_trace.event("append_shed", pending=self._pending,
                                max_pending=self._max_pending)
                raise IngestOverloadedError(self._pending,
                                            self._max_pending)
            self._pending += 1
        t0 = time.perf_counter()
        try:
            return self._append_locked(pid, pk, value, event_epoch, t0)
        finally:
            with self._pending_lock:
                self._pending -= 1

    def _append_locked(self, pid, pk, value, event_epoch,
                       t0) -> AppendResult:
        pid = np.asarray(pid)
        pk = np.asarray(pk)
        value = None if value is None else np.asarray(value)
        n = len(pid)
        if n == 0:
            raise ValueError(
                "empty append: an empty batch has no content identity "
                "to dedup on; use advance_watermark to move event time "
                "without rows")
        if len(pk) != n or (value is not None and len(value) != n):
            raise ValueError(
                f"column lengths disagree: pid={n} pk={len(pk)}"
                + (f" value={len(value)}" if value is not None else ""))
        for col_name, col in (("pid", pid), ("pk", pk),
                              ("value", value)):
            if col is not None and col.dtype.kind not in "iuf":
                raise ValueError(
                    f"live appends take numeric columns only "
                    f"({col_name} has dtype {col.dtype}); epoch "
                    f"payloads are allow_pickle=False npz")
        digest = streaming.input_digest(pid, pk, value)
        store, name = self._store_binding
        # Phase A (under _append_lock): validate, write the epoch
        # payload + WAL record (flushed, not yet fsync'd), stage. The
        # fsync itself happens OUTSIDE the lock so concurrent appends
        # coalesce behind one group commit instead of serializing on
        # per-record fsyncs.
        dup_staged = None
        with self._append_lock:
            self._check_open()
            # Idempotency FIRST — before event assignment, so a blind
            # re-submit of a committed batch never re-enters as a new
            # (possibly late) event. Promotion mutates the committed
            # maps under self._lock, so read them under it too.
            with self._lock:
                prior_epoch = self._digests.get(digest)
                prior = (self._epochs[prior_epoch]
                         if prior_epoch is not None else None)
                if prior is None:
                    dup_staged = self._staged_digests.get(digest)
                dead = digest in self._deadletters
                eff_max_event = self._max_event
                has_value = self._has_value
                for rec in self._staged.values():
                    eff_max_event = max(eff_max_event,
                                        rec["event_epoch"])
                    if has_value is None:
                        has_value = rec["value_present"]
            if prior is not None:
                profiler.count_event(EVENT_APPEND_DUPLICATES)
                obs_trace.event("append_duplicate", digest=digest)
                obs_metrics.append_seconds().observe(
                    time.perf_counter() - t0)
                return AppendResult(
                    epoch=prior_epoch, digest=digest,
                    n_rows=prior["n_rows"],
                    event_epoch=prior["event_epoch"], committed=False,
                    duplicate=True)
            if dead:
                profiler.count_event(EVENT_APPEND_DUPLICATES)
                obs_metrics.append_seconds().observe(
                    time.perf_counter() - t0)
                return AppendResult(
                    epoch=-1, digest=digest, n_rows=n,
                    event_epoch=(event_epoch if event_epoch is not None
                                 else -1),
                    committed=False, duplicate=True, dead_lettered=True)
            if dup_staged is None:
                if event_epoch is None:
                    event_epoch = eff_max_event + 1
                event_epoch = int(event_epoch)
                if event_epoch < 0:
                    raise ValueError(
                        f"event_epoch must be >= 0, got {event_epoch}")
                horizon = (eff_max_event
                           - self._live_window.allowed_lateness)
                if event_epoch < horizon:
                    return self._handle_late(store, name, digest, pid,
                                             pk, value, event_epoch,
                                             horizon, t0)
                if value is not None and has_value is False or \
                        value is None and has_value is True:
                    raise ValueError(
                        "value column presence must be consistent across "
                        "a live session's appends (the union fold encodes "
                        "one value plan)")
                epoch = self._next_epoch
                with obs_trace.span("serving/append", session=self._name,
                                    epoch=epoch, n_rows=n,
                                    event_epoch=event_epoch):
                    obs_flight.record("append_start", session=self._name,
                                      epoch=epoch, digest=digest,
                                      n_rows=n, event_epoch=event_epoch)
                    # Durable payload, then the pre-commit micro-encode:
                    # re-drives the SlabDriver ingest schedule over JUST
                    # the new rows, so rows that cannot encode (value
                    # overflow, bad ids) fail HERE — before the WAL
                    # record exists, leaving the session untouched at
                    # epoch N.
                    store.save_epoch(name, epoch, pid, pk, value)
                    self._micro_encode(pid, pk, value)
                    _maybe_crash("encode", epoch)
                    # The commit record: written + flushed here; durable
                    # against power loss only after the group fsync
                    # below. "digest" is the WAL's own per-record key;
                    # the batch identity travels as content_digest. A
                    # leased WAL's fence re-checks the on-disk lease
                    # HERE — a superseded ex-primary's append is
                    # refused before the record lands.
                    try:
                        self._wal.append({
                            "seq": self._wal.next_seq, "kind": "append",
                            "epoch": epoch, "content_digest": digest,
                            "n_rows": n, "event_epoch": event_epoch},
                            sync=False)
                    except journal_lib.StaleWriterError:
                        self._fenced_append(store, name, digest, pid,
                                            pk, value, event_epoch)
                        raise
                    _maybe_crash("commit", epoch)
                    ticket = self._wal.sync_ticket()
                    staged = {
                        "epoch": epoch, "digest": digest, "n_rows": n,
                        "event_epoch": event_epoch, "ticket": ticket,
                        "rows": (pid, pk, value),
                        "value_present": value is not None}
                    with self._lock:
                        self._staged[epoch] = staged
                        self._staged_digests[digest] = staged
                    self._next_epoch = epoch + 1
        if dup_staged is not None:
            # A racing append already wrote this batch's WAL record but
            # has not fsync'd yet: ride its group commit, then report
            # the duplicate against the promoted epoch.
            self._wal.sync_through(dup_staged["ticket"])
            self._promote_staged()
            profiler.count_event(EVENT_APPEND_DUPLICATES)
            obs_trace.event("append_duplicate", digest=digest)
            obs_metrics.append_seconds().observe(time.perf_counter() - t0)
            return AppendResult(
                epoch=dup_staged["epoch"], digest=digest,
                n_rows=dup_staged["n_rows"],
                event_epoch=dup_staged["event_epoch"], committed=False,
                duplicate=True)
        # Phase B: THE commit point — the group fsync. One leader
        # fsyncs for every staged append up to its ticket (bounded
        # coalescing window via PIPELINEDP_TPU_APPEND_COMMIT_WINDOW_MS).
        # Before it, the epoch does not exist (against power loss);
        # after it, reopen folds it.
        self._wal.sync_through(ticket,
                               window_s=append_commit_window_s())
        _maybe_crash("fold", epoch)
        # Phase C: ordered promotion into the committed maps
        # (idempotent — whichever thread reaches an epoch first
        # promotes it; epochs promote strictly in order).
        self._promote_staged()
        # Phase D: the coalesced union fold — one re-encode may cover
        # several freshly promoted epochs.
        fingerprint = self._fold_committed()
        profiler.count_event(EVENT_APPENDS)
        obs_flight.record("append_commit", session=self._name,
                          epoch=epoch, digest=digest,
                          fingerprint=fingerprint)
        obs_metrics.append_seconds().observe(time.perf_counter() - t0)
        return AppendResult(epoch=epoch, digest=digest, n_rows=n,
                            event_epoch=event_epoch, committed=True)

    def _fenced_append(self, store, name, digest, pid, pk, value,
                       event_epoch) -> None:
        """A fenced (stale-writer) append's bookkeeping: the batch is
        dead-lettered — quarantined data, never a committed epoch under
        a superseded lease — and counted, before the StaleWriterError
        propagates to the producer. The new primary sees the dead
        letter on its next reopen/poll."""
        profiler.count_event(EVENT_APPENDS_FENCED)
        obs_trace.event("append_fenced", digest=digest,
                        event_epoch=event_epoch)
        obs_flight.record("append_fenced", session=self._name,
                          digest=digest, event_epoch=event_epoch)
        try:
            store.save_deadletter(name, digest, pid, pk, value)
            with self._lock:
                self._deadletters.add(digest)
        except OSError:
            pass  # quarantine is best-effort; the refusal is the point

    def _promote_staged(self) -> None:
        """Moves fsync-covered staged epochs into the committed maps,
        strictly in epoch order (any thread may run this; promotion is
        idempotent under self._lock). A staged epoch promotes once the
        WAL's synced ticket covers its record."""
        synced = self._wal.synced_ticket
        while True:
            with self._lock:
                rec = self._staged.get(len(self._epochs))
                if rec is None or rec["ticket"] > synced:
                    return
                epoch = rec["epoch"]
                self._epochs.append({
                    "epoch": epoch, "digest": rec["digest"],
                    "n_rows": rec["n_rows"],
                    "event_epoch": rec["event_epoch"]})
                self._epoch_rows[epoch] = rec["rows"]
                self._digests[rec["digest"]] = epoch
                self._max_event = max(self._max_event,
                                      rec["event_epoch"])
                if self._has_value is None:
                    self._has_value = rec["value_present"]
                del self._staged[epoch]
                self._staged_digests.pop(rec["digest"], None)

    def _fold_committed(self) -> str:
        """The coalesced in-memory fold: re-encodes the union of the
        committed epochs unless a concurrent fold already covered them
        (one union re-encode may serve several promotions). Returns the
        current wire fingerprint."""
        with self._fold_lock:
            with self._lock:
                target = len(self._epochs)
                if self._folded_epochs >= target:
                    return self._wire.fingerprint
            old_fp = self._wire.fingerprint
            new_wire = self._fold_union()
            with self._lock:
                self._wire = new_wire
                self._folded_epochs = target
                self._sweep_stale_bound_entries(old_fp)
            if (self._mesh is None and new_wire.n_rows > 0
                    and new_wire.host_nbytes <= self._byte_budget):
                new_wire.ensure_device()
            profiler.count_event(EVENT_EPOCH_FOLDS)
            return new_wire.fingerprint

    def _handle_late(self, store, name, digest, pid, pk, value,
                     event_epoch, horizon, t0) -> AppendResult:
        if self._live_window.late_policy == "dead_letter":
            store.save_deadletter(name, digest, pid, pk, value)
            with self._lock:
                self._deadletters.add(digest)
            profiler.count_event(EVENT_LATE_DEADLETTERED)
            obs_trace.event("append_deadlettered", digest=digest,
                            event_epoch=event_epoch, horizon=horizon)
            obs_flight.record("append_deadlettered", session=self._name,
                              digest=digest, event_epoch=event_epoch)
            obs_metrics.append_seconds().observe(time.perf_counter() - t0)
            return AppendResult(epoch=-1, digest=digest, n_rows=len(pid),
                                event_epoch=event_epoch, committed=False,
                                dead_lettered=True)
        profiler.count_event(EVENT_LATE_REJECTED)
        obs_trace.event("append_late_rejected", digest=digest,
                        event_epoch=event_epoch, horizon=horizon)
        raise LateArrivalError(event_epoch, horizon)

    def advance_watermark(self, event_epoch: int) -> None:
        """Durably advances event time without rows (e.g. a quiet
        period that should seal — and release — empty windows). The
        advancement is a WAL record, so reopen replays it."""
        self._ensure_writable("advance_watermark()")
        event_epoch = int(event_epoch)
        if event_epoch < 0:
            raise ValueError(
                f"event_epoch must be >= 0, got {event_epoch}")
        with self._append_lock:
            self._check_open()
            if event_epoch <= self._max_event:
                return  # monotone: never move the watermark backwards
            self._wal.append({"seq": self._wal.next_seq,
                              "kind": "advance",
                              "event_epoch": event_epoch})
            with self._lock:
                self._max_event = event_epoch

    # -- the fold ---------------------------------------------------------

    def _union_rows(self, lo: Optional[int] = None,
                    hi: Optional[int] = None):
        """Concatenated raw rows of the committed epochs (in commit
        order) whose event epoch falls in ``[lo, hi)`` (all when
        unbounded). This union is the dataset a cold run must ingest
        for bit-identity."""
        parts = []
        with self._lock:
            for rec in self._epochs:
                e = rec["event_epoch"]
                if lo is not None and e < lo:
                    continue
                if hi is not None and e >= hi:
                    continue
                parts.append(self._epoch_rows[rec["epoch"]])
        if not parts:
            return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                    np.zeros(0, np.float32) if self._has_value else None)
        pid = np.concatenate([p[0] for p in parts])
        pk = np.concatenate([p[1] for p in parts])
        value = (np.concatenate([p[2] for p in parts])
                 if parts[0][2] is not None else None)
        return pid, pk, value

    def _encode_wire(self, pid, pk, value):
        """The exact cold ingest: encode_rows under the fixed public
        vocabulary, then ingest_resident_wire with the pinned chunk
        schedule and the session's mesh bucket layout."""
        n_dev = self._mesh.devices.size if self._mesh is not None else 1
        if len(pid) == 0:
            return dataclasses.replace(
                streaming._empty_resident_wire(
                    max(len(self._pk_vocab), 1)), n_dev=n_dev)
        e_pid, e_pk, e_value, _, pk_vocab = encoding.encode_rows(
            encoding.ColumnarData(pid=pid, pk=pk, value=value), True,
            None, None, public_partitions=self._public,
            factorize_pid=False)
        self._pk_vocab = pk_vocab
        return streaming.ingest_resident_wire(
            e_pid, e_pk, e_value, num_partitions=max(len(pk_vocab), 1),
            n_chunks=self._live_n_chunks, n_dev=n_dev)

    def _micro_encode(self, pid, pk, value) -> None:
        """The pre-commit gate: re-drives the SlabDriver ingest schedule
        over JUST the new rows (same encoder, pinned chunk count). Rows
        that cannot encode fail here — before the WAL commit point — so
        a poisoned batch can never become a committed epoch the reopen
        fold would then choke on."""
        with obs_trace.span("serving/micro_encode", session=self._name,
                            n_rows=len(pid)):
            self._encode_wire(pid, pk, value)

    def _fold_union(self):
        with obs_trace.span("dp/ingest", session=self._name,
                            epochs=len(self._epochs)):
            return self._encode_wire(*self._union_rows())

    def _sweep_stale_bound_entries(self, old_fp: str) -> None:
        """Epoch bump invalidation (caller holds self._lock): drops the
        full-wire bound entries keyed to the pre-fold fingerprint.
        Sealed-window entries carry a ("window", a, b, fp) prefix and
        survive — their wires are immutable once sealed."""
        stale = [k for k in self._bound_cache
                 if isinstance(k[0], tuple) and k[0][:1] == ("wire_fp",)
                 and k[0][1] == old_fp]
        for k in stale:
            self._cache_bytes -= self._bound_cache.pop(k).nbytes

    def _accumulate(self, k_kernel, *, mesh, resilience=None, **kw):
        # Full-session queries tag their bound entries with the live
        # wire's fingerprint: a fold invalidates exactly them.
        wire = self._wire
        return self._accumulate_wire(
            wire, ("wire_fp", wire.fingerprint), k_kernel, mesh=mesh,
            resilience=resilience, **kw)

    def _batch_key_prefix(self):
        # query_batch's planner keys must match _accumulate's cache
        # keys exactly, or batch-warmed entries would never hit (and a
        # fold's sweep would miss them).
        return ("wire_fp", self._wire.fingerprint)

    # -- window queries ---------------------------------------------------

    def window_wire(self, a: int, b: int):
        """The sealed window's ResidentWire — the union of its rows
        through the cold ingest (cached per window; immutable once
        sealed, which is why only sealed windows are queryable)."""
        if not self.is_sealed(a, b):
            raise ValueError(
                f"window [{a},{b}) is not sealed (sealed horizon "
                f"{self.sealed_horizon}): querying an open window would "
                f"give non-deterministic answers; append more data or "
                f"advance_watermark past {b + self._live_window.allowed_lateness}")
        key = (a, b)
        with self._lock:
            wire = self._window_wires.get(key)
        if wire is not None:
            return wire
        pid, pk, value = self._union_rows(a, b)
        wire = self._encode_wire(pid, pk, value)
        with self._lock:
            self._window_wires[key] = wire
        return wire

    def window_query(self, a: int, b: int, params, *,
                     epsilon: Optional[float] = None, delta: float = 0.0,
                     seed: int = 0, tenant: Optional[str] = None,
                     **query_kwargs):
        """One DP query over the sealed window ``[a, b)`` — bit-identical
        to the same query over the window's rows ingested cold with the
        session's pinned chunk count. Tenant charges carry the window's
        ledger tag, so per-window budget caps (register_tenant's
        window_epsilon/window_delta) apply."""
        view = _WindowView(self, self.window_wire(a, b), a, b)
        binding = _LiveBinding(view=view, window_tag=f"w[{a},{b})")
        return self.query(params, epsilon=epsilon, delta=delta, seed=seed,
                          tenant=tenant, _live=binding, **query_kwargs)

    # -- persistence ------------------------------------------------------

    def save(self, store=None) -> str:
        path = super().save(store)
        # super().save rebuilt the manifest from scratch; restore the
        # live section so open() keeps refusing and open_live keeps
        # finding the window configuration.
        store, name = self._store_binding
        store.record_live(name, self._live_meta())
        return path

    def release_schedule(self, schedule_id: str, params, *,
                         epsilon: float, delta: float = 0.0,
                         tenant: str, base_seed: int = 0,
                         empty_policy: str = "release",
                         **query_kwargs) -> "ReleaseSchedule":
        """A continual-release schedule over this session's sealed
        windows (see :class:`ReleaseSchedule`). Recreating it with the
        same ``schedule_id`` after a reopen reattaches its outcome WAL —
        recorded windows stay released, missed ones catch up on the
        next :meth:`~ReleaseSchedule.tick`."""
        return ReleaseSchedule(self, schedule_id, params,
                               epsilon=epsilon, delta=delta,
                               tenant=tenant, base_seed=base_seed,
                               empty_policy=empty_policy,
                               query_kwargs=query_kwargs)

    @classmethod
    def _reopen(cls, store, name: str, manifest: dict, *, mesh=None,
                resident_bytes=None, epilogue_cache=None,
                read_only: bool = False) -> "LiveDatasetSession":
        """SessionStore.open_live's worker: WAL replay -> digest-checked
        epoch payloads -> one union fold. Lands at exactly the epoch
        the WAL committed; the stored wire.npz (a point-in-time spill)
        is ignored — the WAL is authoritative.

        ``read_only=True`` builds a follower replica: the WAL is
        scanned with the truncation-free ``journal.read_records`` (a
        live primary may still be appending — the follower must never
        truncate its torn tail or open it for append), no WAL handle or
        audit binding is created, and the replica keeps a replication
        cursor for :meth:`apply_wal_payloads`."""
        live = manifest["live"]
        n_dev = mesh.devices.size if mesh is not None else 1
        if manifest["n_dev"] != n_dev:
            raise ValueError(
                f"session {name!r} was created for n_dev="
                f"{manifest['n_dev']}; opening with n_dev={n_dev} "
                f"cannot replay it (pass the matching mesh)")
        knobs = manifest["knobs"]
        public = manifest["public_partitions"]
        vocab = encoding.Vocabulary(list(public))
        self = cls._restore(
            dataclasses.replace(
                streaming._empty_resident_wire(max(len(vocab), 1)),
                n_dev=n_dev),
            vocab,
            public_partitions=public, mesh=mesh, name=manifest["name"],
            secure_host_noise=knobs["secure_host_noise"],
            segment_sort=knobs["segment_sort"],
            compact_merge=knobs["compact_merge"],
            resident_bytes=resident_bytes,
            epilogue_cache=epilogue_cache,
            store_binding=None if read_only else (store, name))
        self._init_live(WindowSpec.from_meta(live["window"]),
                        int(live["n_chunks"]), None)
        if read_only:
            # Late-bind the store WITHOUT _bind_audit (no append handle
            # on the primary's audit WAL) and without a _wal handle.
            self._store_binding = (store, name)
            self._read_only = True
            payloads = journal_lib.read_records(
                store.append_wal_path(name))
        else:
            self._wal = journal_lib.JsonlWal(store.append_wal_path(name))
            payloads = self._wal.recovered
        for payload in payloads:
            self._apply_wal_payload(payload, store, name)
        self._applied_wal_records = len(payloads)
        self._deadletters = set(store.deadletter_digests(name))
        self._next_epoch = len(self._epochs)
        self._wire = self._fold_union()
        self._folded_epochs = len(self._epochs)
        if (mesh is None and self._wire.n_rows > 0
                and self._wire.host_nbytes <= self._byte_budget):
            self._wire.ensure_device()
        return self

    def close(self) -> None:
        super().close()
        if self._wal is not None:
            self._wal.close()
            self._wal = None


def window_seed(base_seed: int, a: int, b: int) -> int:
    """The deterministic per-window seed of a ReleaseSchedule: derived
    from (base_seed, window bounds) alone, so catch-up after a crash
    re-derives the same seed — and the release journal can recognize a
    replay of the same window's token."""
    h = hashlib.sha256(f"{base_seed}:{a}:{b}".encode()).digest()
    return int.from_bytes(h[:4], "big") % (2 ** 31 - 1)


class ReleaseSchedule:
    """Continual DP releases over a live session's sealed windows,
    exactly once across restarts.

    Each :meth:`tick` answers every sealed-but-unrecorded window in
    order (one query per window, deterministic per-window seed) and
    records the outcome on the schedule's own fsync'd WAL — *after* the
    release, so a crash in between errs toward an unrecorded window
    whose catch-up re-run is refused by the tenant's at-most-once
    release journal (``DoubleReleaseError``) and recorded as
    ``"recovered"``; the charge is exactly refunded. Windows with no
    rows default to ``empty_policy="release"`` (a noise-only release
    over the public partitions — *suppressing* them would leak that the
    window was empty, which is data; "suppress" is available for
    pipelines whose emptiness is public knowledge).

    A deliberate :meth:`replay` of a recorded window surfaces the
    ``DoubleReleaseError`` to the caller — the refusal IS the contract.
    """

    def __init__(self, session: LiveDatasetSession, schedule_id: str,
                 params, *, epsilon: float, delta: float = 0.0,
                 tenant: str, base_seed: int = 0,
                 empty_policy: str = "release",
                 query_kwargs: Optional[dict] = None):
        if empty_policy not in ("release", "suppress"):
            raise ValueError(
                f"empty_policy must be 'release' or 'suppress', got "
                f"{empty_policy!r}")
        if tenant is None:
            raise ValueError(
                "a ReleaseSchedule needs a tenant: the tenant's "
                "at-most-once release journal is what refuses "
                "cross-restart replays, and its ledger carries the "
                "per-window budget")
        session._ensure_writable("release_schedule()")
        session.tenant(tenant)  # fail fast on unknown tenants
        store, name = session.store_binding
        self._session = session
        self._id = schedule_id
        self._params = params
        self._epsilon = epsilon
        self._delta = delta
        self._tenant = tenant
        self._base_seed = base_seed
        self._empty_policy = empty_policy
        self._query_kwargs = dict(query_kwargs or {})
        self._wal = journal_lib.JsonlWal(store.schedule_path(name,
                                                             schedule_id))
        # The schedule's outcome WAL is fenced like every other WAL of
        # a leased session: a superseded primary cannot record (or
        # sync) outcomes a successor now owns.
        fence = session._wal_fence()
        if fence is not None:
            self._wal.attach_fence(fence)
        self._recorded: Dict[tuple, str] = {}
        for payload in self._wal.recovered:
            self._recorded[(int(payload["a"]), int(payload["b"]))] = \
                payload["outcome"]

    @property
    def schedule_id(self) -> str:
        return self._id

    @property
    def recorded(self) -> Dict[tuple, str]:
        """{(a, b): outcome} of every recorded window."""
        return dict(self._recorded)

    def due_windows(self) -> List[tuple]:
        """Sealed windows with no recorded outcome, in order — what the
        next tick will answer (catch-up after a reopen included)."""
        return [w for w in self._session.sealed_windows()
                if w not in self._recorded]

    def tick(self) -> List[dict]:
        """Releases every due window; returns one record per window:
        {"window": (a, b), "outcome": "released" | "recovered" |
        "suppressed", "seed": int, "result": columns or None}.

        An admission shed / deadline / engine failure propagates with
        the window left unrecorded (its charge already exactly
        refunded by the query path) — the next tick retries it."""
        out = []
        for a, b in self.due_windows():
            t0 = time.perf_counter()
            wseed = window_seed(self._base_seed, a, b)
            with obs_trace.span("serving/release_tick",
                                session=self._session.name,
                                schedule=self._id, a=a, b=b):
                record = self._release_window(a, b, wseed)
            self._wal.append({"seq": self._wal.next_seq, "a": a, "b": b,
                              "outcome": record["outcome"],
                              "seed": wseed})
            self._recorded[(a, b)] = record["outcome"]
            obs_metrics.release_tick_seconds().observe(
                time.perf_counter() - t0)
            obs_flight.record("release_tick",
                              session=self._session.name,
                              schedule=self._id, a=a, b=b,
                              outcome=record["outcome"])
            out.append(record)
        return out

    def _release_window(self, a: int, b: int, wseed: int) -> dict:
        record = {"window": (a, b), "seed": wseed, "result": None}
        wire = self._session.window_wire(a, b)
        if wire.n_rows == 0 and self._empty_policy == "suppress":
            profiler.count_event(EVENT_RELEASES_SUPPRESSED)
            record["outcome"] = "suppressed"
            return record
        try:
            result = self._session.window_query(
                a, b, self._params, epsilon=self._epsilon,
                delta=self._delta, seed=wseed, tenant=self._tenant,
                **self._query_kwargs)
            record["result"] = result.to_columns()
            record["outcome"] = "released"
            profiler.count_event(EVENT_SCHEDULED_RELEASES)
            # The harness's crash seam between release and record:
            # reopen finds the window due, re-runs it, and the release
            # journal's refusal becomes outcome "recovered".
            _maybe_crash("release", a)
        except journal_lib.DoubleReleaseError:
            # The window's token committed before a crash wiped the
            # outcome record: the release already happened (or was
            # about to — the journal errs toward "never twice"), the
            # charge was exactly refunded by the query path. Record,
            # don't re-draw.
            record["outcome"] = "recovered"
            profiler.count_event(EVENT_RELEASES_RECOVERED)
        return record

    def replay(self, a: int, b: int):
        """Deliberately re-runs a recorded window — which the tenant's
        release journal refuses with DoubleReleaseError. Exists so
        operators (and tests) can PROVE the at-most-once property
        rather than trust it."""
        if (a, b) not in self._recorded:
            raise ValueError(
                f"window [{a},{b}) has no recorded outcome; nothing to "
                f"replay — tick() releases due windows")
        wseed = window_seed(self._base_seed, a, b)
        return self._session.window_query(
            a, b, self._params, epsilon=self._epsilon, delta=self._delta,
            seed=wseed, tenant=self._tenant, **self._query_kwargs)

    def close(self) -> None:
        self._wal.close()
