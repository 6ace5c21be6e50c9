"""DatasetSession: the resident-dataset serving layer (SERVING.md).

One session = one ingested dataset + any number of DP queries against
it. Ingest pays the dominant e2e phases (host encode, per-bucket radix
sort, and — for device-resident handles — the host->device transfer)
exactly once; queries replay the retained wire through the chunk
kernels, and repeat queries with identical bounding configuration skip
even the kernel via the session's accumulator ("bound") cache.

Exactness contract: a query answered from a session is BIT-IDENTICAL —
released values and kept partitions — to the same query run cold through
``JaxDPEngine(accountant, seed=s, stream_chunks=session.n_chunks, ...)``
on the source columns, on single-device and on a mesh. The bound cache
preserves this automatically: its key includes the kernel-key
fingerprint, so a hit replays exactly the accumulators that key would
have produced.

Thread-safety: ``query`` may be called concurrently from many threads.
Shared state (the bound cache, tenant ledgers and journals, the
epilogue cache, profiler counters) is lock-guarded; everything else
(engine, accountant, result) is per-query local. Two racing misses of
the same bound-cache key may both compute — they produce identical
arrays, so the race costs work, never correctness.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from pipelinedp_tpu import budget_accounting
from pipelinedp_tpu import combiners as combiners_lib
from pipelinedp_tpu import jax_engine
from pipelinedp_tpu import profiler
from pipelinedp_tpu.aggregate_params import (AggregateParams, MechanismType,
                                             Metric, Metrics, NoiseKind)
from pipelinedp_tpu.obs import audit as audit_lib
from pipelinedp_tpu.obs import flight as obs_flight
from pipelinedp_tpu.obs import metrics as obs_metrics
from pipelinedp_tpu.obs import trace as obs_trace
from pipelinedp_tpu.ops import columnar, encoding, finalize as finalize_ops
from pipelinedp_tpu.ops import streaming
from pipelinedp_tpu.runtime import checkpoint as checkpoint_lib
from pipelinedp_tpu.runtime import journal as journal_lib
from pipelinedp_tpu.runtime import retry as retry_lib
from pipelinedp_tpu.runtime import watchdog as watchdog_lib

# Tuning knobs (validated via native.loader.env_int; README "Tuning
# knobs" + SERVING.md):
#   PIPELINEDP_TPU_RESIDENT_BYTES — per-session resident byte budget:
#     the wire slab goes device-resident when it fits, and the bound
#     cache LRU-evicts to stay under what remains (default 1 GiB).
#   PIPELINEDP_TPU_SERVING_BATCH — max query configs packed into one
#     vmapped launch by query_batch (default 32).
#   PIPELINEDP_TPU_QUERY_DEADLINE_S — default per-query deadline in
#     seconds (0 = none): an expired query surfaces as a typed,
#     retryable QueryDeadlineError instead of running (or hanging)
#     unboundedly.
#   PIPELINEDP_TPU_EPILOGUE_WORKERS — bounded executor width for the
#     pipelined per-config finalizes of query_batch (default 2; 0 runs
#     epilogues synchronously). Released bits are identical at every
#     width: the plan fixes commit order and per-config keys before any
#     epilogue runs.
RESIDENT_BYTES_ENV = "PIPELINEDP_TPU_RESIDENT_BYTES"
BATCH_WIDTH_ENV = "PIPELINEDP_TPU_SERVING_BATCH"
DEADLINE_ENV = "PIPELINEDP_TPU_QUERY_DEADLINE_S"
EPILOGUE_WORKERS_ENV = "PIPELINEDP_TPU_EPILOGUE_WORKERS"

# Profiler event counters (profiler.count_event / event_count; the
# replay-side counters live in ops/streaming.py, the fleet-level
# admission/demotion counters in serving/manager.py):
EVENT_QUERIES = "serving/queries"
EVENT_BOUND_HITS = "serving/bound_cache_hits"
EVENT_BOUND_MISSES = "serving/bound_cache_misses"
EVENT_BOUND_EVICTIONS = "serving/bound_cache_evictions"
# Graceful degradation: device-resident replays that hit
# RESOURCE_EXHAUSTED and fell back to host-window shipping instead of
# failing the query.
EVENT_DEVICE_FALLBACKS = "serving/device_fallbacks"
# Queries that tripped their per-query deadline (QueryDeadlineError).
EVENT_DEADLINE_HITS = "serving/query_deadline_hits"
# Spilled sessions re-hydrated from the store on demand.
EVENT_REHYDRATIONS = "serving/sessions_rehydrations"
# Slow-query capture bundles written (obs/flight.py; PR 13).
EVENT_SLOW_CAPTURES = "serving/slow_query_captures"
# Query-plane (serving/planner.py) counters: batch configs that skipped
# replay on a bound-cache hit, configs that deduped onto another
# config's replay lane, and fused launch groups compiled.
EVENT_PLANNER_CACHE_SKIPS = "serving/planner_cache_skips"
EVENT_PLANNER_DEDUPES = "serving/planner_dedupes"
EVENT_PLANNER_GROUPS = "serving/planner_fused_groups"

# Per-process query trace ids: "q<pid>-<n>". The same id lands on the
# query's root span (attr "qid"), its flight-recorder events, its audit
# record (trace_id) and any slow-query capture file — the correlation
# key of the operational plane. Never derived from data.
_QUERY_IDS = itertools.count()


def _next_query_id() -> str:
    return f"q{os.getpid()}-{next(_QUERY_IDS)}"


def resident_byte_budget() -> int:
    """Validated PIPELINEDP_TPU_RESIDENT_BYTES (default 1 GiB)."""
    from pipelinedp_tpu.native import loader
    return loader.env_int(RESIDENT_BYTES_ENV, 1 << 30, 1 << 20, 1 << 40)


def batch_width() -> int:
    """Validated PIPELINEDP_TPU_SERVING_BATCH (default 32): the max
    configs one vmapped launch carries; wider batches split."""
    from pipelinedp_tpu.native import loader
    return loader.env_int(BATCH_WIDTH_ENV, 32, 1, 1024)


def epilogue_workers() -> int:
    """Validated PIPELINEDP_TPU_EPILOGUE_WORKERS (default 2): executor
    width for query_batch's pipelined per-config finalizes; 0 disables
    the overlap (epilogues run synchronously after their group)."""
    from pipelinedp_tpu.native import loader
    return loader.env_int(EPILOGUE_WORKERS_ENV, 2, 0, 32)


def default_deadline_s() -> Optional[float]:
    """Validated PIPELINEDP_TPU_QUERY_DEADLINE_S (None when 0/unset)."""
    from pipelinedp_tpu.native import loader
    seconds = loader.env_int(DEADLINE_ENV, 0, 0, 24 * 3600)
    return float(seconds) if seconds > 0 else None


def serving_counters() -> Dict[str, int]:
    """Snapshot of the serving counters (the fleet-level
    admission/demotion counters ride serving.fleet_counters())."""
    return {
        "queries": profiler.event_count(EVENT_QUERIES),
        "bound_cache_hits": profiler.event_count(EVENT_BOUND_HITS),
        "bound_cache_misses": profiler.event_count(EVENT_BOUND_MISSES),
        "bound_cache_evictions": profiler.event_count(
            EVENT_BOUND_EVICTIONS),
        "wire_replays": profiler.event_count(
            streaming.EVENT_SERVING_REPLAYS),
        "kernel_dispatches": profiler.event_count(
            streaming.EVENT_SERVING_LAUNCHES),
        "device_fallbacks": profiler.event_count(EVENT_DEVICE_FALLBACKS),
        "query_deadline_hits": profiler.event_count(EVENT_DEADLINE_HITS),
        "slow_query_captures": profiler.event_count(EVENT_SLOW_CAPTURES),
        "planner_cache_skips": profiler.event_count(
            EVENT_PLANNER_CACHE_SKIPS),
        "planner_dedupes": profiler.event_count(EVENT_PLANNER_DEDUPES),
        "planner_fused_groups": profiler.event_count(EVENT_PLANNER_GROUPS),
    }


class StaleDatasetError(RuntimeError):
    """The source columns were mutated after ingest: the retained wire no
    longer describes the data the caller is looking at, so the session
    refuses to answer (re-ingest to serve the new data)."""


class SessionClosedError(RuntimeError):
    """The session was closed; its handle and caches are gone."""


class SessionReadOnlyError(RuntimeError):
    """A mutating operation on a ``read_only=True`` (follower) session:
    followers hold no single-writer lease, so append/save/tenant/spill
    paths refuse — promote to primary first (serving/fleet.py)."""


@dataclasses.dataclass
class TenantState:
    """One tenant's serving-side state: the cross-query budget ledger and
    the at-most-once release journal. Tenants never share either — one
    tenant replaying a release or exhausting its epsilon cannot touch
    another tenant's ledger or journal."""
    ledger: budget_accounting.TenantBudgetLedger
    release_journal: journal_lib.ReleaseJournal


@dataclasses.dataclass
class QueryConfig:
    """One query of a batched launch (DatasetSession.query_batch).

    Configs in one batch share the session's sorted wire and pack into a
    single vmapped kernel launch per chunk; metrics / epsilon / clip
    bounds / caps / seed / tenant vary per config.
    """
    metrics: List[Metric]
    epsilon: float
    delta: float = 0.0
    noise_kind: NoiseKind = NoiseKind.LAPLACE
    max_partitions_contributed: Optional[int] = None
    max_contributions_per_partition: Optional[int] = None
    max_contributions: Optional[int] = None
    min_value: Optional[float] = None
    max_value: Optional[float] = None
    min_sum_per_partition: Optional[float] = None
    max_sum_per_partition: Optional[float] = None
    seed: int = 0
    tenant: Optional[str] = None

    def to_params(self) -> AggregateParams:
        return AggregateParams(
            metrics=list(self.metrics),
            noise_kind=self.noise_kind,
            max_partitions_contributed=self.max_partitions_contributed,
            max_contributions_per_partition=self.
            max_contributions_per_partition,
            max_contributions=self.max_contributions,
            min_value=self.min_value,
            max_value=self.max_value,
            min_sum_per_partition=self.min_sum_per_partition,
            max_sum_per_partition=self.max_sum_per_partition)


@dataclasses.dataclass
class _BoundCacheEntry:
    result: Any  # accs, or (accs, qhist)
    nbytes: int


@dataclasses.dataclass
class _PreparedQuery:
    """One config's engine-side state, prepared before the batched
    accumulate (budget requests registered, keys drawn, caps derived)."""
    index: int
    engine: Any
    accountant: Any
    compound: Any
    sel_spec: Any
    params: AggregateParams
    k_kernel: Any
    k_select: Any
    k_noise: Any
    key_counter: int
    linf_cap: int
    l0_cap: int
    l1_cap: Optional[int]
    row_lo: float
    row_hi: float
    glo: float
    ghi: float
    middle: float
    need_flags: tuple
    has_group_clip: bool
    # Tenant bookkeeping for exact refunds on a failed batch: the
    # pre-run ledger charge and the TenantState it was charged against
    # (None for non-tenant configs).
    state: Any = None
    charge: Any = None
    # Query-plane routing (serving/planner.py): the config's resolved
    # bound-cache key, and the wall-clock duration of ITS replay +
    # finalize (set when its epilogue completes; audit falls back to
    # the batch duration when the config never finished).
    bound_key: Any = None
    duration_s: Optional[float] = None


@jax.jit
def _unstack_lanes(accs_b) -> tuple:
    """The B lanes of a batched [B, num_partitions] fold as B
    PartitionAccumulators, in one program. Slicing lane by lane and field
    by field dispatches B x 5 programs behind the batched replay, and on
    a TPU those fill the device's queue: the host then waits for the
    replay to drain before it can hand any lane to an epilogue."""
    return tuple(columnar.PartitionAccumulators(*(a[b] for a in accs_b))
                 for b in range(accs_b[0].shape[0]))


class DatasetSession:
    """A resident dataset serving many DP queries (module docstring).

    data: ColumnarData or EncodedColumns (use :meth:`from_frame` for
      pandas / dict frames).
    public_partitions: fixed at ingest — the public filter and the
      partition vocabulary shape the wire, so every query of the session
      shares them.
    mesh: a ``parallel.sharded.make_mesh`` mesh; the wire is ingested in
      the mesh's bucket layout and queries replay sharded. Device
      residency (skipping per-query transfer) is single-device only.
    n_chunks: wire chunk count; defaults to the streaming path's own
      choice for this row count, so cold-parity engines need
      ``stream_chunks=session.n_chunks``.
    resident_bytes: overrides PIPELINEDP_TPU_RESIDENT_BYTES.
    verify_source: keep a reference to the source columns and refuse
      queries (StaleDatasetError) if their digest no longer matches the
      ingest-time fingerprint. Costs one O(n) column-sum per query.
    """

    # Duck-typed marker JaxDPEngine.aggregate dispatches on (keeps the
    # engine free of serving imports).
    is_resident_dataset = True

    def __init__(self,
                 data,
                 *,
                 public_partitions: Optional[Sequence[Any]] = None,
                 mesh=None,
                 n_chunks: Optional[int] = None,
                 resident_bytes: Optional[int] = None,
                 value_transfer_dtype=None,
                 secure_host_noise: bool = True,
                 segment_sort="auto",
                 compact_merge="auto",
                 epilogue_cache: Optional[
                     finalize_ops.EpilogueCache] = None,
                 verify_source: bool = True,
                 name: str = "dataset"):
        self._init_common(name=name, mesh=mesh,
                          public_partitions=public_partitions,
                          secure_host_noise=secure_host_noise,
                          segment_sort=segment_sort,
                          compact_merge=compact_merge,
                          epilogue_cache=epilogue_cache,
                          resident_bytes=resident_bytes)

        with obs_trace.span("dp/ingest", session=name):
            pid, pk, value, _, pk_vocab = encoding.encode_rows(
                data, True, None, None,
                public_partitions=self._public, factorize_pid=False)
            self._pk_vocab = pk_vocab
            n_dev = mesh.devices.size if mesh is not None else 1
            self._wire = streaming.ingest_resident_wire(
                pid, pk, value,
                num_partitions=max(len(pk_vocab), 1),
                n_chunks=n_chunks, n_dev=n_dev,
                value_transfer_dtype=value_transfer_dtype)
        if verify_source:
            self._source = data
            self._source_digest = checkpoint_lib.array_digest(
                np.asarray(data.pid), np.asarray(data.pk),
                None if data.value is None else np.asarray(data.value))
        else:
            self._source = self._source_digest = None
        # Device residency: the sorted wire moves onto the device when it
        # fits the byte budget, so warm queries skip the host->device
        # transfer too. Mesh handles stay host-side (each chunk ships
        # sharded per query).
        if (mesh is None and self._wire.n_rows > 0
                and self._wire.host_nbytes <= self._byte_budget):
            self._wire.ensure_device()

    def _init_common(self, *, name, mesh, public_partitions,
                     secure_host_noise, segment_sort, compact_merge,
                     epilogue_cache, resident_bytes) -> None:
        """State shared by ingest (__init__) and store re-hydration
        (:meth:`_restore`)."""
        self._name = name
        self._mesh = mesh
        self._public = (list(public_partitions)
                        if public_partitions is not None else None)
        self._secure_host_noise = secure_host_noise
        self._segment_sort = segment_sort
        self._compact_merge = compact_merge
        self._epilogue_cache = (epilogue_cache if epilogue_cache is not None
                                else finalize_ops.default_cache())
        self._byte_budget = (int(resident_bytes) if resident_bytes is not None
                             else resident_byte_budget())
        self._closed = False
        self._lock = threading.Lock()
        self._bound_cache: "collections.OrderedDict[tuple, _BoundCacheEntry]"
        self._bound_cache = collections.OrderedDict()
        self._cache_bytes = 0
        self._tenants: Dict[str, TenantState] = {}
        self._queries = 0
        # Query-plane accounting (serving/planner.py): cumulative plan
        # stats + replay/epilogue wall time for the overlap ratio.
        self._planner_totals = {
            "batches": 0, "configs": 0, "cache_skips": 0, "dedupes": 0,
            "lanes": 0, "fused_groups": 0, "replay_s": 0.0,
            "epilogue_s": 0.0, "wall_s": 0.0}
        self._frame_meta = None  # set by from_frame
        # Durable-fleet state (serving/store.py, serving/manager.py):
        #   _store_binding — (SessionStore, name) after save()/open();
        #   _manager — the SessionManager this session is admitted to;
        #   _spilled — wire bytes live only in the store (rung 3 of the
        #     demotion ladder); queries re-hydrate on demand;
        #   _active — queries currently executing (spill never unloads a
        #     handle a replay is reading);
        #   _lifecycle_lock — serializes spill / re-hydrate / query
        #     start+finish, so lifecycle transitions and replays never
        #     interleave;
        #   _deadline_tls — the running query's Deadline, read by
        #     _accumulate on whatever thread executes the replay.
        self._store_binding = None
        self._manager = None
        # Fleet tier (serving/fleet.py):
        #   _lease — the SessionLease a writable store-bound open holds
        #     (its admit() fences every WAL append on live sessions);
        #   _read_only — a follower replica: every mutating path
        #     refuses with SessionReadOnlyError.
        self._lease = None
        self._read_only = False
        self._spilled = False
        self._active = 0
        self._lifecycle_lock = threading.Lock()
        self._deadline_tls = threading.local()
        # Release audit trail (obs/audit.py): in-memory until the
        # session is store-bound, then durable under the store
        # (_bind_audit) so outcomes survive process death.
        self._audit = audit_lib.AuditTrail()

    @classmethod
    def _restore(cls, wire: streaming.ResidentWire,
                 pk_vocab: encoding.Vocabulary, *,
                 public_partitions, mesh, name: str,
                 secure_host_noise: bool, segment_sort, compact_merge,
                 resident_bytes: Optional[int],
                 epilogue_cache: Optional[finalize_ops.EpilogueCache],
                 store_binding) -> "DatasetSession":
        """A session over an already-validated wire handle — the store's
        re-hydration path (serving/store.py). No ingest runs, no source
        columns exist (``verify_source`` has nothing to verify: the wire
        was digest-validated against its fingerprint on load)."""
        self = cls.__new__(cls)
        self._init_common(name=name, mesh=mesh,
                          public_partitions=public_partitions,
                          secure_host_noise=secure_host_noise,
                          segment_sort=segment_sort,
                          compact_merge=compact_merge,
                          epilogue_cache=epilogue_cache,
                          resident_bytes=resident_bytes)
        self._pk_vocab = pk_vocab
        self._wire = wire
        self._source = self._source_digest = None
        self._store_binding = store_binding
        self._bind_audit()
        if (mesh is None and wire.n_rows > 0 and wire.loaded
                and wire.host_nbytes <= self._byte_budget):
            wire.ensure_device()
        return self

    # -- construction from L5 frames ------------------------------------

    @classmethod
    def from_frame(cls, df, privacy_unit_column: str, partition_key,
                   value_column: Optional[str] = None, *,
                   public_keys: Optional[Sequence[Any]] = None,
                   **session_kwargs) -> "DatasetSession":
        """Ingests a pandas DataFrame or dict-of-arrays frame, fixing the
        (privacy unit, partition key, value) columns for the session's
        lifetime. ``QueryBuilder.on(session)`` then builds declarative
        queries against it (dataframes.py)."""
        from pipelinedp_tpu import dataframes

        converter = dataframes._create_converter(df)
        names = converter.column_names(df)
        for col in ([privacy_unit_column] + dataframes._as_list(
                partition_key) + ([value_column] if value_column else [])):
            if col not in names:
                raise ValueError(f"Column {col} is not present in the frame")
        columns = dataframes.Columns(privacy_unit_column, partition_key,
                                     value_column)
        data = converter.frame_to_columns(df, columns)
        session = cls(data, public_partitions=public_keys,
                      **session_kwargs)
        session._frame_meta = {
            "converter": converter,
            "column_names": list(names),
            "privacy_unit_column": privacy_unit_column,
            "partition_key": dataframes._as_list(partition_key),
            "value_column": value_column,
        }
        return session

    # -- introspection ---------------------------------------------------

    @property
    def name(self) -> str:
        return self._name

    @property
    def pk_vocab(self) -> encoding.Vocabulary:
        return self._pk_vocab

    @property
    def n_rows(self) -> int:
        return self._wire.n_rows

    @property
    def num_partitions(self) -> int:
        return self._wire.num_partitions

    @property
    def n_chunks(self) -> int:
        """Chunk count of the retained wire — the ``stream_chunks=`` a
        cold engine needs for bit-parity with this session."""
        return self._wire.n_chunks

    @property
    def fingerprint(self) -> str:
        """Wire-handle identity (wirecodec.resident_fingerprint)."""
        return self._wire.fingerprint

    @property
    def mesh(self):
        return self._mesh

    @property
    def public_partitions(self):
        return list(self._public) if self._public is not None else None

    @property
    def frame_meta(self) -> Optional[dict]:
        """Frame binding of a from_frame session (None otherwise)."""
        return self._frame_meta

    def stats(self) -> dict:
        """Resident-memory and cache accounting of this session."""
        with self._lock:
            return {
                "wire_host_bytes": self._wire.host_nbytes,
                "wire_device_bytes": self._wire.device_nbytes,
                "bound_cache_bytes": self._cache_bytes,
                "bound_cache_entries": len(self._bound_cache),
                "resident_bytes": (self._wire.host_nbytes
                                   + self._wire.device_nbytes
                                   + self._cache_bytes),
                "byte_budget": self._byte_budget,
                "queries": self._queries,
                "n_chunks": self._wire.n_chunks,
                "spilled": self._spilled,
                "active_queries": self._active,
                "store": (self._store_binding[0].path(self._store_binding[1])
                          if self._store_binding is not None else None),
                "read_only": self._read_only,
                "fleet": ({"lease": self._lease.status()}
                          if self._lease is not None else None),
                "planner": self._planner_stats_locked(),
                "tenants": {
                    tid: {
                        "total_epsilon": st.ledger.total_epsilon,
                        "spent_epsilon": st.ledger.spent_epsilon,
                        "remaining_epsilon": st.ledger.remaining_epsilon,
                        "total_delta": st.ledger.total_delta,
                        "spent_delta": st.ledger.spent_delta,
                        "releases": len(st.release_journal),
                    }
                    for tid, st in self._tenants.items()
                },
            }

    def _planner_stats_locked(self) -> dict:
        """The query-plane sub-dict of stats() (caller holds _lock).

        epilogue_overlap_ratio estimates how much per-config finalize
        time was hidden behind batched replays: with replay + epilogue
        busy time R and E inside total batch wall W, anything past W
        must have run concurrently, so overlap = clamp((R + E - W) / E).
        0.0 = fully sequential, 1.0 = every epilogue hidden."""
        t = self._planner_totals
        overlap = 0.0
        if t["epilogue_s"] > 0.0:
            overlap = (t["replay_s"] + t["epilogue_s"] - t["wall_s"]
                       ) / t["epilogue_s"]
            overlap = max(0.0, min(1.0, overlap))
        return {
            "batches": t["batches"],
            "configs": t["configs"],
            "cache_skips": t["cache_skips"],
            "dedupes": t["dedupes"],
            "lanes": t["lanes"],
            "fused_groups": t["fused_groups"],
            "epilogue_overlap_ratio": round(overlap, 4),
        }

    def close(self) -> None:
        """Frees the handle (device + host) and every cache; further
        queries raise SessionClosedError. A held single-writer lease is
        released (marked, not deleted — the next acquire takes over
        immediately instead of waiting out the TTL)."""
        with self._lock:
            self._closed = True
            self._wire.drop_device()
            self._bound_cache.clear()
            self._cache_bytes = 0
            self._source = None
        if self._lease is not None:
            try:
                self._lease.release()
            except OSError:
                pass  # best effort: expiry reclaims it anyway
        self._audit.close()

    def __enter__(self) -> "DatasetSession":
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> None:
        self.close()

    # -- persistence & fleet lifecycle (serving/store.py, manager.py) ----

    @property
    def is_spilled(self) -> bool:
        """True when the wire bytes live only in the session store (the
        demotion ladder's disk rung); the next query re-hydrates."""
        return self._spilled

    @property
    def store_binding(self):
        """(SessionStore, stored name) after save()/open(), else None."""
        return self._store_binding

    @property
    def audit_trail(self) -> audit_lib.AuditTrail:
        """The session's release audit trail (obs/audit.py): one record
        per finished query with mechanism kinds, (ε, δ), kept/dropped
        partition counts, timing and a typed outcome. Durable (WAL
        under the store) once the session is store-bound."""
        return self._audit

    @property
    def read_only(self) -> bool:
        """True for a follower replica (serving/fleet.py): no lease, no
        WAL handles; every mutating path refuses."""
        return self._read_only

    @property
    def lease(self):
        """The held SessionLease of a writable store-bound open (None
        for leaseless or read-only sessions)."""
        return self._lease

    def _ensure_writable(self, what: str) -> None:
        if self._read_only:
            raise SessionReadOnlyError(
                f"session {self._name!r} is a read-only follower "
                f"replica; {what} needs the single-writer lease — "
                f"promote first (serving/fleet.py)")

    def _wal_fence(self):
        """The fence callable for this session's WALs (None when no
        lease is held — leaseless legacy opens stay unfenced)."""
        return self._lease.admit if self._lease is not None else None

    def _attach_lease(self, lease) -> None:
        """Binds an acquired SessionLease; live sessions additionally
        fence their WALs (the override in serving/live.py)."""
        self._lease = lease

    def _bind_audit(self) -> None:
        """Moves the audit trail onto its durable WAL under the bound
        store (idempotent; the in-memory prefix is replayed onto disk),
        and binds the process flight recorder's spool/dump next to the
        WALs — so a SIGKILL'd or wedged process leaves its post-mortem
        where its durable state lives (obs/flight.py)."""
        if self._store_binding is None:
            return
        store, name = self._store_binding
        obs_flight.ensure_process_spool(store.flight_dir())
        if self._audit.durable:
            return
        self._audit.bind(store.audit_path(name))

    def save(self, store=None) -> str:
        """Spills the session durably: wire chunks (per-chunk digested),
        bound-cache entries (content-digested), tenant registrations —
        and migrates every tenant's release journal and budget ledger
        onto fsync'd WALs under the store, so ``SessionStore.open``
        after process death re-hydrates a session whose warm queries are
        bit-identical and whose cross-restart release/spend replays are
        still refused. Returns the on-disk session path. The session
        stays fully usable (saving is not spilling)."""
        if store is None:
            if self._store_binding is None:
                raise ValueError(
                    "session has no bound store; pass save(store=)")
            store = self._store_binding[0]
        self._check_open()
        self._ensure_writable("save()")
        with obs_trace.span("fleet/save", session=self._name):
            path = store.save(self)
        self._bind_audit()
        return path

    def spill(self, store=None) -> bool:
        """Demotes the session to the disk rung: saves (if needed) and
        frees the wire bytes (host and device) and the in-memory bound
        cache. Returns False — and keeps everything — when a query is
        executing (a replay must never lose the slab under its feet).
        The persisted bound entries re-hydrate with the wire."""
        if self._read_only:
            return False  # followers keep their replica resident
        with self._lifecycle_lock:
            if self._active > 0:
                return False
            if self._spilled:
                return True
            self.save(store)
            with self._lock:
                self._wire.unload()
                self._bound_cache.clear()
                self._cache_bytes = 0
                self._spilled = True
            return True

    def rehydrate(self) -> None:
        """Loads the wire bytes (and persisted bound entries) back from
        the bound store; idempotent. Chunk digests are validated against
        the handle's fingerprint — a corrupted spill refuses
        (SessionCorruptError) rather than serving wrong bits; corrupted
        bound entries are dropped and recompute via kernel replay."""
        with self._lifecycle_lock:
            self._rehydrate_locked()

    def _rehydrate_locked(self) -> None:
        if not self._spilled:
            return
        t0 = time.perf_counter()
        with obs_trace.span("fleet/rehydrate", session=self._name):
            store, name = self._store_binding
            slab, bound_entries = store.load_payload(name)
            with self._lock:
                self._check_open()
                self._wire.reload(slab)
                self._spilled = False
            profiler.count_event(EVENT_REHYDRATIONS)
            if (self._mesh is None and self._wire.n_rows > 0
                    and self._wire.host_nbytes <= self._byte_budget):
                self._wire.ensure_device()
            for key, result in bound_entries:
                self._cache_insert(key, result)
        obs_metrics.rehydration_seconds().observe(
            time.perf_counter() - t0)

    def demote_device(self) -> bool:
        """Demotion rung 1: frees the device copy of the wire (the host
        slab stays authoritative; queries re-ship windows)."""
        with self._lock:
            if not self._wire.device_resident:
                return False
            self._wire.drop_device()
            return True

    @contextlib.contextmanager
    def _pinned(self):
        """Query-lifetime pin: re-hydrates a spilled session, then holds
        ``_active`` > 0 so a concurrent spill can never unload the slab
        a replay is reading. The manager is notified *after* the
        lifecycle lock drops (its budget enforcement takes other
        sessions' lifecycle locks — never while we hold ours)."""
        with self._lifecycle_lock:
            was_spilled = self._spilled
            if was_spilled:
                self._rehydrate_locked()
            with self._lock:
                self._active += 1
        try:
            if self._manager is not None:
                self._manager.notify_used(self, rehydrated=was_spilled)
            yield
        finally:
            with self._lock:
                self._active -= 1

    # -- integrity -------------------------------------------------------

    def verify_source(self) -> None:
        """Refuses a mutated source dataset: recomputes the source-column
        digest and compares it to the ingest-time fingerprint (the same
        evidence checkpoint resume uses to refuse mutated inputs)."""
        if self._source is None:
            return
        with obs_trace.span("dp/verify_source", session=self._name):
            digest = checkpoint_lib.array_digest(
                np.asarray(self._source.pid), np.asarray(self._source.pk),
                None if self._source.value is None else np.asarray(
                    self._source.value))
        if digest != self._source_digest:
            raise StaleDatasetError(
                f"session {self._name!r}: the source columns changed "
                f"after ingest (digest {digest} != ingest "
                f"{self._source_digest}); the retained wire no longer "
                f"describes this data — re-ingest to serve it")

    def _check_open(self) -> None:
        if self._closed:
            raise SessionClosedError(
                f"session {self._name!r} is closed")

    def _check_engine_compat(self, engine, public_partitions) -> None:
        """Engine-side gate (called from JaxDPEngine._aggregate)."""
        self._check_open()
        if engine._mesh is not self._mesh:
            raise ValueError(
                "engine mesh does not match the session's ingest mesh; "
                "a resident wire replays only on the topology it was "
                "ingested for")
        pub = (list(public_partitions)
               if public_partitions is not None else None)
        if pub != self._public:
            raise ValueError(
                "public_partitions differ from the session's: the public "
                "filter and partition vocabulary are fixed at ingest")
        self.verify_source()

    # -- tenants ---------------------------------------------------------

    def register_tenant(self, tenant_id: str, total_epsilon: float,
                        total_delta: float = 0.0,
                        release_journal: Optional[
                            journal_lib.ReleaseJournal] = None,
                        window_epsilon: Optional[float] = None,
                        window_delta: Optional[float] = None
                        ) -> TenantState:
        """Creates a tenant with its own cross-query budget ledger and
        at-most-once release journal (a FileReleaseJournal makes the
        tenant's release history survive process death).

        On a store-bound session (after save()/open()) both are durable
        by default: the release journal and the ledger land on fsync'd
        WALs under the store, and the registration is recorded in the
        session manifest immediately — so a crash right after
        registration still reattaches the tenant on reopen.

        ``window_epsilon``/``window_delta`` cap the spend attributable to
        any single release window on a live session (charges tagged with
        a window label by the continual-release scheduler); untagged
        queries see only the total caps."""
        self._ensure_writable("register_tenant()")
        with self._lock:
            self._check_open()
            if tenant_id in self._tenants:
                raise ValueError(f"tenant {tenant_id!r} already registered")
            wal = None
            if self._store_binding is not None:
                store, name = self._store_binding
                if release_journal is None:
                    release_journal = journal_lib.FileReleaseJournal(
                        store.tenant_release_path(name, tenant_id))
                wal = journal_lib.FileReleaseJournal(
                    store.tenant_ledger_path(name, tenant_id))
            state = TenantState(
                ledger=budget_accounting.TenantBudgetLedger(
                    tenant_id, total_epsilon, total_delta, wal=wal,
                    window_epsilon=window_epsilon,
                    window_delta=window_delta),
                release_journal=(release_journal if release_journal
                                 is not None else
                                 journal_lib.ReleaseJournal()))
            self._tenants[tenant_id] = state
        if self._store_binding is not None:
            store, name = self._store_binding
            store.record_tenant(name, tenant_id, total_epsilon, total_delta,
                                release_journal,
                                window_epsilon=window_epsilon,
                                window_delta=window_delta)
        return state

    def tenant(self, tenant_id: str) -> TenantState:
        with self._lock:
            if tenant_id not in self._tenants:
                raise ValueError(
                    f"tenant {tenant_id!r} is not registered; call "
                    f"register_tenant first")
            return self._tenants[tenant_id]

    # -- the bound (accumulator) cache -----------------------------------

    @staticmethod
    def _canonical(v):
        if isinstance(v, (tuple, list)):
            return tuple(DatasetSession._canonical(x) for x in v)
        if isinstance(v, np.generic):
            return v.item()
        return v

    def _cache_key(self, key_fp: str, kw: dict) -> tuple:
        return (key_fp,) + tuple(
            (k, self._canonical(kw[k])) for k in sorted(kw))

    def _resolved_sampler(self, mesh, kw: dict, wire=None) -> str:
        """The RESOLVED sampler this query config compiles against
        (streaming.resolved_sampler_desc), cached under the bound-cache
        key so flipping ``segment_sort`` between queries — e.g. two
        user-built engines over one session, or "auto" resolving
        differently for different caps — can never alias a cached
        accumulator produced by a different group stage."""
        wire = self._wire if wire is None else wire
        num_partitions = wire.num_partitions
        if mesh is not None:
            from pipelinedp_tpu.parallel import sharded
            num_partitions = sharded.padded_num_partitions(
                mesh, num_partitions)
        return streaming.resolved_sampler_desc(
            wire.fmt, kw.get("segment_sort", "auto"),
            wire.max_run, num_partitions=num_partitions,
            row_clip_lo=kw.get("row_clip_lo", -np.inf),
            row_clip_hi=kw.get("row_clip_hi", np.inf),
            linf_cap=kw.get("linf_cap", 1),
            l1_mode=kw.get("l1_cap") is not None,
            group_clip_lo=kw.get("group_clip_lo", -np.inf),
            group_clip_hi=kw.get("group_clip_hi", np.inf),
            need_flags=kw.get("need_flags", (True, True, True, True)))

    @staticmethod
    def _result_nbytes(result) -> int:
        arrays = []
        if isinstance(result, tuple) and not hasattr(result, "_fields"):
            accs, qhist = result
            arrays.extend(accs)
            if qhist is not None:
                arrays.append(qhist)
        else:
            arrays.extend(result)
        return int(sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in arrays))

    def _accumulate(self, k_kernel, *, mesh, resilience=None, **kw):
        """Accumulators for one query config — from the bound cache when
        this exact (kernel key, caps, clips, flags) was computed before
        (a hit is bitwise-exact by construction: the key includes the
        kernel-key fingerprint), replaying the retained wire otherwise.
        Called by JaxDPEngine._execute on the resident path."""
        return self._accumulate_wire(self._wire, None, k_kernel,
                                     mesh=mesh, resilience=resilience,
                                     **kw)

    def _accumulate_wire(self, wire, key_prefix, k_kernel, *, mesh,
                         resilience=None, **kw):
        """The replay-or-cache body of :meth:`_accumulate`, parameterized
        by the wire so live sessions can route window views through the
        same machinery. ``key_prefix`` (a tuple or None) is prepended to
        the bound-cache key — live sessions tag entries with the wire
        fingerprint so an epoch bump invalidates only the entries the
        fold actually changed.

        A running query's Deadline (thread-local, set by :meth:`query`)
        is injected into the replay's resilience bundle so the slab
        driver checks it cooperatively between windows. A
        device-resident replay that hits RESOURCE_EXHAUSTED degrades
        gracefully: the device copy is dropped and the replay re-issues
        with host-window shipping — same chunk kernels, same keys, same
        released bits, one fallback counter richer."""
        key_fp = checkpoint_lib.key_fingerprint(k_kernel)
        # The sampler enters the key as its RESOLVED identity, not the
        # raw knob string: knobs that compile the same kernel share the
        # entry ("auto" vs "hash" under the gate), knobs that compile
        # different group stages can never alias.
        kw_for_key = {k: v for k, v in kw.items() if k != "segment_sort"}
        cache_key = self._cache_key(key_fp, kw_for_key) + (
            ("resolved_sampler", self._resolved_sampler(mesh, kw, wire)),)
        if key_prefix is not None:
            cache_key = (key_prefix,) + cache_key
        with self._pinned():
            with self._lock:
                self._check_open()
                entry = self._bound_cache.get(cache_key)
                if entry is not None:
                    self._bound_cache.move_to_end(cache_key)
                    profiler.count_event(EVENT_BOUND_HITS)
                    obs_trace.event("bound_cache_hit")
                    return entry.result
            profiler.count_event(EVENT_BOUND_MISSES)
            deadline = getattr(self._deadline_tls, "value", None)
            if deadline is not None:
                if resilience is None:
                    from pipelinedp_tpu import runtime as runtime_lib
                    resilience = runtime_lib.StreamResilience()
                resilience.deadline = deadline
            t_replay0 = time.perf_counter()
            with obs_trace.span("serving/replay", session=self._name,
                                n_chunks=wire.n_chunks):
                try:
                    result = self._replay(k_kernel, mesh, resilience, kw,
                                          wire)
                except Exception as exc:
                    if (retry_lib.classify(exc) != retry_lib.OOM
                            or not wire.device_resident):
                        raise
                    # Graceful degradation: a device-resident replay that
                    # exhausted device memory falls back to shipping host
                    # windows instead of failing the query.
                    wire.drop_device()
                    profiler.count_event(EVENT_DEVICE_FALLBACKS)
                    obs_trace.event("device_fallback")
                    result = self._replay(k_kernel, mesh, resilience, kw,
                                          wire)
            obs_metrics.replay_seconds().observe(
                time.perf_counter() - t_replay0)
            self._cache_insert(cache_key, result)
            return result

    def _replay(self, k_kernel, mesh, resilience, kw, wire=None):
        wire = self._wire if wire is None else wire
        if mesh is not None:
            from pipelinedp_tpu.parallel import sharded
            mesh_kw = dict(kw)
            if mesh_kw.pop("quantile_spec", None) is not None:
                raise NotImplementedError(
                    "quantile replay is single-device only")
            return sharded.replay_resident_wire(
                mesh, k_kernel, wire, resilience=resilience,
                **mesh_kw)
        return streaming.replay_resident_wire(
            k_kernel, wire, resilience=resilience, **kw)

    def _cache_insert(self, cache_key: tuple, result) -> None:
        nbytes = self._result_nbytes(result)
        with self._lock:
            if self._closed or cache_key in self._bound_cache:
                return
            room = self._byte_budget - self._wire.device_nbytes
            if nbytes > room:
                return  # never evict the whole cache for one giant entry
            while self._cache_bytes + nbytes > room and self._bound_cache:
                _, evicted = self._bound_cache.popitem(last=False)
                self._cache_bytes -= evicted.nbytes
                profiler.count_event(EVENT_BOUND_EVICTIONS)
            self._bound_cache[cache_key] = _BoundCacheEntry(
                result=result, nbytes=nbytes)
            self._cache_bytes += nbytes

    # -- queries ---------------------------------------------------------

    def query(self,
              params: AggregateParams,
              *,
              epsilon: Optional[float] = None,
              delta: float = 0.0,
              seed: int = 0,
              tenant: Optional[str] = None,
              accountant: Optional[
                  budget_accounting.BudgetAccountant] = None,
              secure_host_noise: Optional[bool] = None,
              release_journal: Optional[
                  journal_lib.ReleaseJournal] = None,
              deadline_s: Optional[float] = None,
              fault_injector=None,
              watchdog_timeout_s: Optional[float] = None,
              retry_policy=None,
              trace_path: Optional[str] = None,
              out_explain_computation_report=None,
              _live=None
              ) -> jax_engine.LazyJaxResult:
        """Answers one DP query from the resident dataset.

        Budget comes from ``tenant=`` (charged against the tenant's
        ledger; releases go through the tenant's at-most-once journal),
        an explicit ``accountant=``, or a fresh NaiveBudgetAccountant
        over (epsilon, delta). The result is fully materialized before
        returning: failures surface HERE, so a tenant charge whose
        release token never committed is exactly refunded (the ledger,
        bound cache and journal are left as if the query never ran).

        ``deadline_s`` (default: the manager's deadline, else
        PIPELINEDP_TPU_QUERY_DEADLINE_S) bounds the query end to end:
        the slab driver checks the deadline between windows, and the
        whole replay+finalize runs under a DispatchWatchdog with the
        remaining budget — so even a *wedged* replay surfaces as a
        typed, retryable ``QueryDeadlineError`` within the deadline. A
        timed-out attempt is abandoned, not interrupted: its charge is
        conservatively kept (the abandoned worker could still commit a
        release), which is the same "err toward spent, never toward
        double-release" stance the at-most-once journal takes.

        ``fault_injector`` / ``watchdog_timeout_s`` / ``retry_policy``
        thread straight into the replay's slab driver (the same
        resilience surface a cold streamed run has — chaos and
        kill-harness coverage extends to serving through them).

        Operational plane (PR 13): every query gets a process-unique
        trace id ("q<pid>-<n>") that lands on its root span, its
        flight-recorder events, and its audit record — and when the
        query exceeds ``PIPELINEDP_TPU_SLOW_QUERY_S`` or lands within
        20% of its deadline (either outcome), a capture bundle (Chrome
        trace when tracing is on, metrics delta, flight-recorder
        slice) is written into the bounded
        ``PIPELINEDP_TPU_CAPTURE_DIR``, named by that trace id.

        Observability (OBSERVABILITY.md): the query runs under a
        ``serving/query`` root span (admission → replay → finalize
        children), lands one latency observation in the
        ``pipelinedp_tpu_query_seconds`` histogram, and appends one
        typed-outcome record to the session's audit trail — all
        regardless of success. ``trace_path`` writes THIS query's span
        tree as Chrome trace JSON when a tracer is installed
        (``obs.trace.install()`` / ``PIPELINEDP_TPU_TRACE``); it is a
        no-op otherwise. None of this can change released bits: spans
        read clocks, never data or keys.
        """
        self._check_open()
        if deadline_s is None:
            deadline_s = (self._manager.default_deadline_s
                          if self._manager is not None else None)
            if deadline_s is None:
                deadline_s = default_deadline_s()
        journal = release_journal
        state = charge = None
        if tenant is not None:
            if accountant is not None:
                raise ValueError(
                    "pass either tenant= or accountant=, not both")
            if epsilon is None:
                raise ValueError("tenant queries need epsilon= (the "
                                 "slice charged to the tenant's ledger)")
            state = self.tenant(tenant)
            # Charge-before-run (the at-most-once stance): the slice is
            # spent before any work happens — and exactly refunded below
            # if the query dies before its release token commits.
            charge = state.ledger.charge(
                epsilon, delta, note=f"query seed={seed}",
                window=(_live.window_tag if _live is not None else None))
            accountant = budget_accounting.NaiveBudgetAccountant(
                epsilon, delta)
            if journal is None:
                journal = state.release_journal
        elif accountant is None:
            if epsilon is None:
                raise ValueError(
                    "pass epsilon= (and delta=), an accountant=, or a "
                    "tenant=")
            accountant = budget_accounting.NaiveBudgetAccountant(
                epsilon, delta)
        shn = (self._secure_host_noise
               if secure_host_noise is None else secure_host_noise)
        engine = jax_engine.JaxDPEngine(
            accountant,
            seed=seed,
            secure_host_noise=shn,
            mesh=self._mesh,
            stream_chunks=(_live.view.n_chunks if _live is not None
                           else self._wire.n_chunks),
            segment_sort=self._segment_sort,
            compact_merge=self._compact_merge,
            epilogue_cache=self._epilogue_cache,
            release_journal=journal,
            fault_injector=fault_injector,
            watchdog_timeout_s=watchdog_timeout_s,
            retry_policy=retry_policy)

        deadline = (watchdog_lib.Deadline.after(deadline_s)
                    if deadline_s is not None else None)

        def run_query():
            # Runs on the watchdog worker when a deadline is set; the
            # thread-local hands the Deadline to _accumulate on whatever
            # thread executes the replay.
            self._deadline_tls.value = deadline
            try:
                target = self if _live is None else _live.view
                result = engine.aggregate(
                    target, params, public_partitions=self._public,
                    out_explain_computation_report=(
                        out_explain_computation_report))
                accountant.compute_budgets()
                result.to_columns()  # materialize: replay + finalize
                return result
            finally:
                self._deadline_tls.value = None

        gate = (self._manager.admission()
                if self._manager is not None else contextlib.nullcontext())
        qid = _next_query_id()
        # Slow-query capture bookkeeping (only when a capture dir is
        # configured — the disabled path pays two None checks): the
        # flight watermark and event-counter snapshot scope the capture
        # to THIS query (taken before query_start so the slice holds
        # the full lifecycle).
        cap_dir = obs_flight.capture_dir()
        cap_mark = obs_flight.recorder().watermark() if cap_dir else 0
        cap_events0 = (obs_metrics.default_registry().event_values()
                       if cap_dir else None)
        obs_flight.record("query_start", qid=qid, session=self._name,
                          seed=seed, tenant=tenant or "",
                          deadline_s=deadline_s)
        t_q0 = time.perf_counter()
        root_span = None
        try:
            with obs_trace.span("serving/query", session=self._name,
                                seed=seed, tenant=tenant or "",
                                n_metrics=len(params.metrics),
                                qid=qid) as root_span:
                with contextlib.ExitStack() as stack:
                    with obs_trace.span(
                            "serving/admission",
                            managed=self._manager is not None):
                        stack.enter_context(gate)
                    if deadline is None:
                        result = run_query()
                    else:
                        result = self._run_with_deadline(
                            run_query, deadline, seed, root_span)
        except BaseException as exc:
            if isinstance(exc, watchdog_lib.QueryDeadlineError):
                profiler.count_event(EVENT_DEADLINE_HITS)
            self._maybe_refund(state, charge, journal, engine, exc)
            outcome = self._failure_outcome(exc)
            duration_s = time.perf_counter() - t_q0
            if outcome == "refunded":
                # An unhandled engine error (not a typed fleet outcome):
                # leave the flight-recorder post-mortem while the ring
                # still holds the failing query's events.
                obs_flight.dump_now("engine_error")
            self._finish_query_obs(
                engine=engine, params=params, tenant=tenant,
                accountant=accountant, seed=seed, outcome=outcome,
                duration_s=duration_s, qid=qid)
            self._maybe_capture(qid, root_span, outcome, duration_s,
                                deadline_s, cap_dir, cap_mark,
                                cap_events0, seed=seed, tenant=tenant)
            raise
        duration_s = time.perf_counter() - t_q0
        self._finish_query_obs(
            engine=engine, params=params, tenant=tenant,
            accountant=accountant, seed=seed, outcome="released",
            duration_s=duration_s, qid=qid,
            cols=result.to_columns())
        self._maybe_capture(qid, root_span, "released", duration_s,
                            deadline_s, cap_dir, cap_mark, cap_events0,
                            seed=seed, tenant=tenant)
        if trace_path is not None and root_span is not None:
            tracer = obs_trace.active()
            if tracer is not None:
                tracer.write_chrome(trace_path,
                                    trace_id=root_span.trace_id)
        with self._lock:
            self._queries += 1
        profiler.count_event(EVENT_QUERIES)
        return result

    def _run_with_deadline(self, run_query, deadline, seed,
                           parent_span=None):
        """The whole query under a DispatchWatchdog whose budget is the
        remaining deadline: a wedged replay (which never reaches the
        driver's cooperative between-window check) is abandoned and
        surfaced as QueryDeadlineError within the deadline."""
        wd = watchdog_lib.DispatchWatchdog(
            max(deadline.remaining_s(), 1e-3))
        parent_sinks = profiler.current_sinks()

        def guarded():
            # The watchdog worker joins the query's stage-time sinks AND
            # its span tree (cross-thread parent handoff).
            with profiler.adopt_sinks(parent_sinks), \
                    obs_trace.attach(parent_span):
                return run_query()

        try:
            return wd.call(f"query (session {self._name!r}, seed={seed})",
                           guarded)
        except watchdog_lib.QueryDeadlineError:
            raise  # the driver's cooperative check, already typed
        except watchdog_lib.DispatchHangError as exc:
            raise watchdog_lib.QueryDeadlineError(
                exc.what, deadline.total_s,
                postmortem=exc.postmortem) from exc
        finally:
            wd.close()

    def _maybe_refund(self, state, charge, journal, engine, exc) -> None:
        """Exact refund of a charge whose query provably released
        nothing (SERVING.md "Fleet operation" failure isolation):

        * a refused replay (DoubleReleaseError) drew nothing in THIS
          query — refund;
        * a deadline abandonment might still commit+draw on the
          abandoned worker — conservatively keep the charge;
        * otherwise the release token is checked against the journal:
          not committed means no noise was drawn — refund.
        """
        if state is None or charge is None:
            return
        if isinstance(exc, journal_lib.DoubleReleaseError):
            state.ledger.refund(charge)
            return
        if isinstance(exc, watchdog_lib.QueryDeadlineError):
            return
        token = finalize_ops.release_token(engine._key_stream.fingerprint(),
                                           engine._key_stream.counter)
        if journal is None or not journal.has(token):
            state.ledger.refund(charge)

    @staticmethod
    def _failure_outcome(exc) -> str:
        """The audit-trail outcome of a failed query (obs/audit.py
        OUTCOMES): every failure that refunds reads ``refunded``; the
        typed fleet failures keep their own names."""
        if isinstance(exc, journal_lib.DoubleReleaseError):
            return "double-release-refused"
        if isinstance(exc, watchdog_lib.QueryDeadlineError):
            return "deadline-expired"
        from pipelinedp_tpu.serving import manager as manager_lib
        if isinstance(exc, manager_lib.SessionOverloadedError):
            return "shed"
        return "refunded"

    def _finish_query_obs(self, *, engine, params, tenant, accountant,
                          seed, outcome, duration_s, qid="",
                          cols=None) -> None:
        """One query's telemetry epilogue: the e2e latency observation,
        the flight-recorder outcome event, and the audit record (which
        carries ``qid`` as its ``trace_id`` correlation key). ``cols``
        (released columns) is only present for the ``released``
        outcome; kept/dropped counts are read off the DP output
        (already-released information), never off raw data. -1 marks
        "query produced no output"."""
        obs_metrics.query_seconds().observe(duration_s, outcome=outcome)
        obs_flight.record("query_finish", qid=qid, session=self._name,
                          outcome=outcome,
                          duration_ms=round(duration_s * 1000.0, 3))
        kept = dropped = -1
        if cols is not None:
            keep = np.asarray(cols["keep_mask"])
            kept = int(keep.sum())
            dropped = int(keep.size) - kept
        token = finalize_ops.release_token(
            engine._key_stream.fingerprint(), engine._key_stream.counter)
        with obs_trace.span("dp/audit_append", outcome=outcome):
            self._audit.record(
                session=self._name, tenant=tenant, token=str(token),
                outcome=outcome,
                mechanisms=[str(m) for m in params.metrics],
                noise_kind=getattr(params.noise_kind, "value",
                                   str(params.noise_kind)),
                epsilon=float(accountant.total_epsilon),
                delta=float(accountant.total_delta),
                partitions_kept=kept, partitions_dropped=dropped,
                duration_s=duration_s, seed=seed, trace_id=qid)

    def _maybe_capture(self, qid, root_span, outcome, duration_s,
                       deadline_s, cap_dir, cap_mark, cap_events0, *,
                       seed, tenant) -> None:
        """Slow-query capture (OBSERVABILITY.md "Operational plane"): a
        query that exceeded PIPELINEDP_TPU_SLOW_QUERY_S, or landed
        within 20% of its deadline (expired ones included), writes a
        full post-hoc bundle — Chrome trace (when tracing is on),
        metrics delta, flight-recorder slice — into the bounded capture
        directory, named by the query's trace id. Purely a read of
        already-recorded telemetry: it cannot change released bits, and
        write failures are swallowed (a capture is never worth a
        query)."""
        if cap_dir is None:
            return
        slow_s = obs_flight.slow_query_threshold_s()
        near_deadline = (deadline_s is not None
                         and duration_s >= 0.8 * float(deadline_s))
        if not ((slow_s is not None and duration_s >= slow_s)
                or near_deadline):
            return
        events_after = obs_metrics.default_registry().event_values()
        before = cap_events0 or {}
        metrics_delta = {k: v - before.get(k, 0)
                         for k, v in events_after.items()
                         if v != before.get(k, 0)}
        chrome = None
        tracer = obs_trace.active()
        if tracer is not None and root_span is not None:
            chrome = tracer.export_chrome(trace_id=root_span.trace_id)
        document = {
            "version": 1,
            "trace_id": qid,
            "session": self._name,
            "seed": seed,
            "tenant": tenant,
            "outcome": outcome,
            "duration_s": duration_s,
            "deadline_s": deadline_s,
            "slow_query_s": slow_s,
            "near_deadline": near_deadline,
            "metrics_delta": metrics_delta,
            "flight_events": [e.to_payload() for e in
                              obs_flight.recorder().events(
                                  since_seq=cap_mark)],
            "chrome_trace": chrome,
        }
        path = obs_flight.write_capture(qid, document, cap_dir)
        if path is not None:
            profiler.count_event(EVENT_SLOW_CAPTURES)
            obs_flight.record("slow_query_capture", qid=qid, path=path)

    # -- batched queries -------------------------------------------------

    _BATCH_UNSUPPORTED = (
        "batched resident queries support the scalar metrics "
        "(COUNT/PRIVACY_ID_COUNT/SUM/MEAN/VARIANCE); run {} through "
        "session.query instead")

    def _prepare_query(self, index: int, cfg: QueryConfig,
                       secure_host_noise: Optional[bool]) -> _PreparedQuery:
        params = cfg.to_params()
        if any(m.is_percentile for m in params.metrics):
            raise NotImplementedError(
                self._BATCH_UNSUPPORTED.format("PERCENTILE"))
        if Metrics.VECTOR_SUM in params.metrics:
            raise NotImplementedError(
                self._BATCH_UNSUPPORTED.format("VECTOR_SUM"))
        journal = None
        state = charge = None
        if cfg.tenant is not None:
            state = self.tenant(cfg.tenant)
            charge = state.ledger.charge(
                cfg.epsilon, cfg.delta,
                note=f"batch query #{index} seed={cfg.seed}")
            accountant = budget_accounting.NaiveBudgetAccountant(
                cfg.epsilon, cfg.delta)
            journal = state.release_journal
        else:
            accountant = budget_accounting.NaiveBudgetAccountant(
                cfg.epsilon, cfg.delta)
        shn = (self._secure_host_noise
               if secure_host_noise is None else secure_host_noise)
        engine = jax_engine.JaxDPEngine(
            accountant, seed=cfg.seed, secure_host_noise=shn,
            mesh=self._mesh, epilogue_cache=self._epilogue_cache,
            release_journal=journal)
        # Budget-request order replays engine.aggregate exactly, so the
        # per-mechanism (eps, delta) splits are identical to a sequential
        # run of the same config.
        with accountant.scope(weight=params.budget_weight):
            compound = combiners_lib.create_compound_combiner(
                params, accountant)
            sel_spec = None
            if (self._public is None
                    and not params.post_aggregation_thresholding):
                sel_spec = accountant.request_budget(
                    mechanism_type=MechanismType.GENERIC)
            accountant._compute_budget_for_aggregation(params.budget_weight)
        key = engine._key_stream.next_key()
        key_counter = engine._key_stream.counter
        k_kernel, k_select, k_noise = jax.random.split(key, 3)
        linf_cap, l0_cap, l1_cap = jax_engine.derive_contribution_caps(
            params, compound, self.n_rows, self.num_partitions)
        row_lo, row_hi, glo, ghi, middle = jax_engine.derive_clip_bounds(
            params)
        return _PreparedQuery(
            index=index, engine=engine, accountant=accountant,
            compound=compound, sel_spec=sel_spec, params=params,
            k_kernel=k_kernel, k_select=k_select, k_noise=k_noise,
            key_counter=key_counter, linf_cap=linf_cap, l0_cap=l0_cap,
            l1_cap=l1_cap,
            row_lo=row_lo, row_hi=row_hi, glo=glo, ghi=ghi, middle=middle,
            need_flags=jax_engine.derive_need_flags(compound),
            has_group_clip=bool(params.bounds_per_partition_are_set),
            state=state, charge=charge)

    def query_batch(self,
                    configs: Sequence[QueryConfig],
                    *,
                    secure_host_noise: Optional[bool] = None,
                    max_width: Optional[int] = None) -> List[dict]:
        """Answers a batch of queries through the query plane
        (serving/planner.py, SERVING.md "Query plane"): the batch is
        compiled to a QueryPlan before any launch — configs whose
        resolved-sampler bound key is already cached skip replay
        entirely, duplicate configs collapse onto one replay lane, and
        the surviving lanes fuse into vmapped launch groups keyed on
        their kernel statics (at most ``max_width`` /
        PIPELINEDP_TPU_SERVING_BATCH lanes per launch). Per-config
        finalizes run on a bounded executor
        (PIPELINEDP_TPU_EPILOGUE_WORKERS) pipelined behind the next
        group's replay; each config commits its release token before
        any noise draw, under its own keys, budget, and journal.

        Works on single-device and mesh sessions. Returns one released
        column dict per config, in input order — value-for-value what
        ``session.query`` (and therefore a cold engine run) releases
        for that config alone, at any executor width.
        """
        self._check_open()
        self.verify_source()
        width = max_width or batch_width()
        shn = (self._secure_host_noise
               if secure_host_noise is None else secure_host_noise)
        gate = (self._manager.admission()
                if self._manager is not None else contextlib.nullcontext())
        # One trace id for the whole batched launch: every config's
        # audit record correlates to the same batch (they share the
        # wire, the launch groups, and the failure domain).
        qid = _next_query_id()
        obs_flight.record("query_batch_start", qid=qid,
                          session=self._name, n_configs=len(configs))
        t_b0 = time.perf_counter()
        with obs_trace.span("serving/query_batch", session=self._name,
                            n_configs=len(configs),
                            qid=qid) as batch_span, \
                gate, self._pinned():
            prepared: List[_PreparedQuery] = []
            results: List[Optional[dict]] = [None] * len(configs)
            try:
                with obs_trace.span("dp/serve/plan", n_configs=len(configs)):
                    for i, cfg in enumerate(configs):
                        prepared.append(
                            self._prepare_query(i, cfg, secure_host_noise))
                    plan, cached_results = self._plan_batch(prepared, width)
                self._execute_plan(plan, prepared, cached_results,
                                   results, shn, batch_span, t_b0)
            except BaseException as exc:
                # Exact refunds for every tenant config whose release
                # token never committed (the failed launch group and any
                # group that never ran); finished configs keep their
                # charge — their releases are out the door.
                for p in prepared:
                    if p.charge is not None and p.state is not None:
                        token = finalize_ops.release_token(
                            p.engine._key_stream.fingerprint(),
                            p.key_counter)
                        if not p.state.release_journal.has(token):
                            p.state.ledger.refund(p.charge)
                self._audit_batch(configs, prepared, results,
                                  time.perf_counter() - t_b0, exc, qid)
                raise
        self._audit_batch(configs, prepared, results,
                          time.perf_counter() - t_b0, None, qid)
        with self._lock:
            self._queries += len(prepared)
        profiler.count_event(EVENT_QUERIES, len(prepared))
        return results  # type: ignore[return-value]

    def _audit_batch(self, configs, prepared, results, duration_s,
                     exc, qid="") -> None:
        """One audit record per prepared batch config. A config whose
        released columns landed in ``results`` (or whose tenant journal
        holds its token) reads ``released``; the rest take the batch
        failure's outcome. Each record carries the config's OWN
        duration (batch start -> its epilogue completion) when it
        finished; configs that never finished record the batch wall
        time."""
        outcome_on_failure = (self._failure_outcome(exc)
                              if exc is not None else "refunded")
        with obs_trace.span("dp/audit_append", n_records=len(prepared)):
            for p in prepared:
                cfg = configs[p.index]
                token = finalize_ops.release_token(
                    p.engine._key_stream.fingerprint(), p.key_counter)
                cols = results[p.index]
                released = cols is not None or (
                    p.state is not None
                    and p.state.release_journal.has(token))
                kept = dropped = -1
                if cols is not None:
                    keep = np.asarray(cols["keep_mask"])
                    kept = int(keep.sum())
                    dropped = int(keep.size) - kept
                self._audit.record(
                    session=self._name, tenant=cfg.tenant, token=str(token),
                    outcome="released" if released else outcome_on_failure,
                    mechanisms=[str(m) for m in cfg.metrics],
                    noise_kind=getattr(cfg.noise_kind, "value",
                                       str(cfg.noise_kind)),
                    epsilon=float(cfg.epsilon), delta=float(cfg.delta),
                    partitions_kept=kept, partitions_dropped=dropped,
                    duration_s=(p.duration_s if p.duration_s is not None
                                else duration_s),
                    seed=cfg.seed, trace_id=qid)

    # -- the query plane (serving/planner.py) ----------------------------

    def _batch_key_prefix(self):
        """Bound-cache key prefix for batched queries (None here; live
        sessions tag entries with the wire fingerprint, matching their
        single-query `_accumulate` override)."""
        return None

    def _batch_kw(self, p: _PreparedQuery) -> dict:
        """The exact kw dict `JaxDPEngine._execute` hands `_accumulate`
        for this config on the resident path — batch bound keys MUST
        alias single-query keys, so this mirrors that call site
        field-for-field (quantile metrics never reach the batch path,
        hence quantile_spec=None)."""
        return dict(
            linf_cap=p.linf_cap, l0_cap=p.l0_cap,
            row_clip_lo=p.row_lo, row_clip_hi=p.row_hi, middle=p.middle,
            group_clip_lo=p.glo, group_clip_hi=p.ghi, l1_cap=p.l1_cap,
            need_flags=p.need_flags, has_group_clip=p.has_group_clip,
            quantile_spec=None, segment_sort=self._segment_sort,
            compact_merge=self._compact_merge)

    def _batch_bound_key(self, p: _PreparedQuery) -> tuple:
        """The bound-cache key `_accumulate_wire` would build for this
        config: a batch cache-skip reads exactly the accumulators the
        sequential query would have read, and a batch lane's insert is
        readable by subsequent single queries."""
        kw = self._batch_kw(p)
        key_fp = checkpoint_lib.key_fingerprint(p.k_kernel)
        kw_for_key = {k: v for k, v in kw.items() if k != "segment_sort"}
        cache_key = self._cache_key(key_fp, kw_for_key) + (
            ("resolved_sampler", self._resolved_sampler(self._mesh, kw)),)
        prefix = self._batch_key_prefix()
        if prefix is not None:
            cache_key = (prefix,) + cache_key
        return cache_key

    def _plan_batch(self, prepared: List[_PreparedQuery], width: int):
        """Compiles the batch into a QueryPlan and fetches the cached
        accumulators of every cache-skip under the lock (so a skip can
        never race an eviction between planning and finalize)."""
        from pipelinedp_tpu.serving import planner as planner_lib
        entries = []
        cached_results: Dict[int, Any] = {}
        with self._lock:
            self._check_open()
            for p in prepared:
                p.bound_key = self._batch_bound_key(p)
                entry = self._bound_cache.get(p.bound_key)
                if entry is not None:
                    self._bound_cache.move_to_end(p.bound_key)
                    cached_results[p.index] = entry.result
                entries.append(planner_lib.PlanEntry(
                    index=p.index, bound_key=p.bound_key,
                    fusion_key=(p.has_group_clip, p.l1_cap is not None),
                    need_flags=tuple(p.need_flags),
                    cached=entry is not None))
        plan = planner_lib.compile_plan(entries, width)
        st = plan.stats
        if st["cache_skips"]:
            profiler.count_event(EVENT_BOUND_HITS, st["cache_skips"])
            profiler.count_event(EVENT_PLANNER_CACHE_SKIPS,
                                 st["cache_skips"])
        if st["lanes"]:
            profiler.count_event(EVENT_BOUND_MISSES, st["lanes"])
        if st["dedupes"]:
            profiler.count_event(EVENT_PLANNER_DEDUPES, st["dedupes"])
        if st["fused_groups"]:
            profiler.count_event(EVENT_PLANNER_GROUPS, st["fused_groups"])
        obs_trace.event("batch_plan", **st)
        with self._lock:
            t = self._planner_totals
            t["batches"] += 1
            for k in ("configs", "cache_skips", "dedupes", "lanes",
                      "fused_groups"):
                t[k] += st[k]
        return plan, cached_results

    def _replay_group_batched(self, group, lanes: List[_PreparedQuery]):
        """One launch group's batched replay (the mesh placement and the
        single-device placement share the call shape)."""
        has_group_clip, has_l1 = group.fusion_key
        kwargs = dict(
            linf_caps=[p.linf_cap for p in lanes],
            l0_caps=[p.l0_cap for p in lanes],
            row_clip_los=[p.row_lo for p in lanes],
            row_clip_his=[p.row_hi for p in lanes],
            middles=[p.middle for p in lanes],
            group_clip_los=[p.glo for p in lanes],
            group_clip_his=[p.ghi for p in lanes],
            l1_caps=[p.l1_cap for p in lanes] if has_l1 else None,
            need_flags=tuple(group.union_flags),
            has_group_clip=has_group_clip)
        keys = [p.k_kernel for p in lanes]
        with obs_trace.span("serving/replay_batched", session=self._name,
                            width=len(lanes), n_chunks=self._wire.n_chunks):
            if self._mesh is not None:
                from pipelinedp_tpu.parallel import sharded
                return sharded.replay_resident_wire_batched(
                    self._mesh, keys, self._wire, **kwargs)
            return streaming.replay_resident_wire_batched(
                keys, self._wire, **kwargs)

    def _lane_accs(self, accs_b) -> List[columnar.PartitionAccumulators]:
        """Each lane's [num_partitions] accumulators out of the batched
        [B, num_partitions] fold, sliced in one program; on a mesh each
        slice is re-laid-out to the partition sharding the sequential
        replay produces."""
        lanes = _unstack_lanes(accs_b)
        if self._mesh is not None:
            from pipelinedp_tpu.parallel import sharded
            part = jax.sharding.NamedSharding(
                self._mesh, sharded._part_spec(self._mesh))
            lanes = [columnar.PartitionAccumulators(
                *(jax.device_put(a, part) for a in accs)) for accs in lanes]
        return list(lanes)

    def _execute_plan(self, plan, prepared: List[_PreparedQuery],
                      cached_results: Dict[int, Any],
                      results: List[Optional[dict]], shn: bool,
                      batch_span, t_b0: float) -> None:
        """Runs a compiled QueryPlan: cache-skips finalize immediately,
        launch groups replay in plan order, and per-config epilogues run
        on the bounded executor double-buffered behind the NEXT group's
        replay (group g's finalizes overlap group g+1's batched fold; at
        most two groups of epilogue work ride behind the replay).

        Released bits are identical at every executor width: the plan
        fixes every config's keys and its commit-before-draw ordering up
        front, and per-config finalize state (engine, accountant,
        epilogue operands) is never shared. Under secure host noise the
        executor narrows to one FIFO worker so the process-global host
        RNG draws in plan order — deterministic for a given plan.

        A failed group raises here; configs whose epilogue never
        committed a release token are exactly refunded by query_batch's
        except path (in-flight epilogues are drained first, so the
        journal check races nothing).
        """
        from concurrent import futures as futures_lib

        by_index = {p.index: p for p in prepared}
        workers = epilogue_workers()
        if shn:
            workers = min(workers, 1)
        if self._mesh is not None:
            # Mesh sessions run epilogues inline: a worker-thread
            # finalize on sharded accumulators, the next group's
            # shard_map replay, and the lane-slice gathers would
            # dispatch multi-device collectives concurrently, and
            # interleaved collective enqueues across the mesh's device
            # threads can deadlock. Plan-level dedupe/fusion still
            # applies; only the overlap is single-device.
            workers = 0
        parent_sinks = profiler.current_sinks()
        stats_lock = threading.Lock()
        epilogue_s = [0.0]
        replay_s = 0.0

        def finalize_one(p: _PreparedQuery, accs) -> None:
            t0 = time.perf_counter()
            # Cross-thread telemetry handoff: the worker joins the batch
            # caller's stage-time sinks and span tree.
            with profiler.adopt_sinks(parent_sinks), \
                    obs_trace.attach(batch_span), \
                    obs_trace.span("dp/serve/epilogue", lane=p.index,
                                   l0=p.params.max_partitions_contributed):
                p.accountant.compute_budgets()
                # At-most-once: the release token commits before any
                # noise draw, through this config's (tenant) journal.
                p.engine._commit_release(p.key_counter)
                results[p.index] = p.engine._fused_finalize(
                    p.compound, p.params, p.sel_spec, p.k_select,
                    p.k_noise, accs, None, None, self.num_partitions,
                    self._public is not None)
            now = time.perf_counter()
            p.duration_s = now - t_b0
            with stats_lock:
                epilogue_s[0] += now - t0

        executor = (futures_lib.ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="pdp-epilogue")
            if workers > 0 else None)
        all_futs: List[Any] = []

        def submit(p: _PreparedQuery, accs) -> None:
            if executor is None:
                finalize_one(p, accs)
            else:
                all_futs.append(executor.submit(finalize_one, p, accs))

        try:
            # Cache-skips first: their accumulators are ready now, so
            # their epilogues fill the executor while the first group's
            # replay compiles and runs.
            for idx in plan.cached_indexes:
                submit(by_index[idx], cached_results[idx])
            group_futs: List[List[Any]] = []
            for g, group in enumerate(plan.groups):
                if g >= 2 and executor is not None:
                    # Double-buffer barrier: group g-2's epilogues must
                    # drain before a third replay piles on (bounds the
                    # in-flight accumulator memory to two groups).
                    for f in group_futs[g - 2]:
                        f.result()
                lanes = [by_index[lane.owner] for lane in group.lanes]
                mark = len(all_futs)
                t_r0 = time.perf_counter()
                with obs_trace.span("dp/serve/replay", width=len(lanes),
                                    n_chunks=self._wire.n_chunks):
                    accs_b = self._replay_group_batched(group, lanes)
                replay_s += time.perf_counter() - t_r0
                with obs_trace.span("dp/serve/lane_slice",
                                    width=len(lanes)):
                    lane_accs = self._lane_accs(accs_b)
                    for b, lane in enumerate(group.lanes):
                        if group.flags_exact[b]:
                            # Populate the bound cache FROM the batch:
                            # this launch computed exactly the owner's
                            # columns (union == own flags), so the lane's
                            # result is what a solo replay would have
                            # cached.
                            self._cache_insert(
                                by_index[lane.owner].bound_key,
                                lane_accs[b])
                for b, lane in enumerate(group.lanes):
                    for idx in lane.indexes:
                        submit(by_index[idx], lane_accs[b])
                group_futs.append(all_futs[mark:])
            for f in all_futs:
                f.result()
        finally:
            if executor is not None:
                # Failure path: drop queued epilogues (their configs
                # never committed — refunded by the caller) and drain
                # running ones, so the refund's journal check is
                # race-free. Success path: everything already drained.
                executor.shutdown(wait=True, cancel_futures=True)
        wall = time.perf_counter() - t_b0
        with self._lock:
            t = self._planner_totals
            t["replay_s"] += replay_s
            t["epilogue_s"] += epilogue_s[0]
            t["wall_s"] += wall
