"""JaxDPEngine: the TPU-native columnar execution engine.

Same public contract as DPEngine.aggregate (params, extractors, budget
accounting, explain reports, lazy results) but executes the whole
aggregation as fused jitted kernels over columnar arrays instead of per-row
dataflow: dictionary-encode keys on host, one fused bound-and-aggregate
kernel (sort + segment reductions), one vectorized partition-selection call,
and one batched noise call per mechanism (SURVEY.md §7 architecture stance).

Budget-accounting parity is structural: the engine builds the exact same
CompoundCombiner as DPEngine (same request_budget calls in the same order,
combiners.py:849-922) and then *reads the mechanism specs off the
combiners* to parameterize the device kernels — so (eps, delta) splits are
identical to the reference path by construction.

The lazy-budget contract survives jit: noise scales/granularities enter the
kernels as runtime scalars, computed from the resolved specs at execution
time (after compute_budgets), so recompilation never depends on budgets.
"""

from __future__ import annotations

import enum
import functools
import math
import time
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pipelinedp_tpu import budget_accounting
from pipelinedp_tpu import combiners as combiners_lib
from pipelinedp_tpu import dp_computations
from pipelinedp_tpu import report_generator as report_generator_lib
from pipelinedp_tpu.aggregate_params import (
    AggregateParams, CalculatePrivateContributionBoundsParams, MechanismType,
    Metrics, NoiseKind, NormKind, PrivateContributionBounds,
    SelectPartitionsParams)
from pipelinedp_tpu import dp_engine as dp_engine_lib
from pipelinedp_tpu.data_extractors import DataExtractors
from pipelinedp_tpu.ops import columnar, encoding, noise as noise_ops
from pipelinedp_tpu.ops import finalize as finalize_ops
from pipelinedp_tpu.ops import streaming
from pipelinedp_tpu.ops import wirecodec
from pipelinedp_tpu.ops import quantiles as quantile_ops
from pipelinedp_tpu.ops import selection as selection_ops
from pipelinedp_tpu import quantile_tree as quantile_tree_lib
from pipelinedp_tpu import partition_selection as ps_lib
from pipelinedp_tpu.report_generator import ExplainComputationReport
from pipelinedp_tpu import noise_core
from pipelinedp_tpu import profiler
from pipelinedp_tpu.obs import metrics as obs_metrics
from pipelinedp_tpu.obs import trace as obs_trace
from pipelinedp_tpu.runtime import driver as driver_lib


def _mechanism_noise_params(spec: budget_accounting.MechanismSpec,
                            sensitivities: dp_computations.Sensitivities):
    """(is_gaussian, scale_or_std, granularity) runtime scalars for a spec."""
    mech = dp_computations.create_additive_mechanism(spec, sensitivities)
    if mech.noise_kind == NoiseKind.GAUSSIAN:
        return True, mech.std, noise_core.gaussian_granularity(mech.std)
    return False, mech.noise_parameter, noise_core.laplace_granularity(
        mech.noise_parameter)


def derive_contribution_caps(params: AggregateParams, compound, n_rows: int,
                             num_partitions: int):
    """(linf_cap, l0_cap, l1_cap) for the bounding kernels.

    The single derivation of the engine's contribution-bound caps from
    the aggregation params + compound combiner (parity:
    DPEngine._create_contribution_bounder, dp_engine.py:285-400), shared
    by the standard aggregate path, the custom-combiner path, and the
    serving layer's batched resident queries — so a batched config's caps
    can never drift from what its sequential run would use.
    """
    if (compound.expects_per_partition_sampling()
            and params.max_contributions_per_partition):
        linf_cap = params.max_contributions_per_partition
    else:
        linf_cap = max(n_rows, 1)
    l0_cap = (params.max_partitions_contributed
              if params.max_partitions_contributed else num_partitions)
    if not params.perform_cross_partition_contribution_bounding:
        # Linf-only bounding (utility-analysis mode): noise stays
        # calibrated to the declared L0 bound, but no partitions drop.
        l0_cap = num_partitions
    l1_cap = None
    if params.max_contributions is not None:
        # L1 bounding: a uniform sample of max_contributions rows per
        # privacy unit across all partitions; Linf/L0 caps disabled.
        l1_cap = params.max_contributions
        linf_cap = max(n_rows, 1)
        l0_cap = num_partitions
    if params.contribution_bounds_already_enforced:
        # The input already satisfies the bounds; apply none.
        linf_cap = max(n_rows, 1)
        l0_cap = num_partitions
    return linf_cap, l0_cap, l1_cap


def derive_need_flags(compound) -> Tuple[bool, bool, bool, bool]:
    """(need_count, need_sum, need_norm, need_norm_sq) — which accumulator
    columns the compound's combiners actually read. Dropped columns save
    two full-HBM segment passes each in the kernel. Shared by _execute
    and the serving layer's batched resident queries."""
    return (
        any(isinstance(c, (combiners_lib.CountCombiner,
                           combiners_lib.MeanCombiner,
                           combiners_lib.VarianceCombiner))
            for c in compound.combiners),
        any(isinstance(c, combiners_lib.SumCombiner)
            for c in compound.combiners),
        any(isinstance(c, (combiners_lib.MeanCombiner,
                           combiners_lib.VarianceCombiner))
            for c in compound.combiners),
        any(isinstance(c, combiners_lib.VarianceCombiner)
            for c in compound.combiners),
    )


def derive_clip_bounds(params: AggregateParams):
    """(row_lo, row_hi, group_lo, group_hi, middle) for the bounding
    kernels, from the params' bounds mode. Shared by _execute and the
    serving layer's batched resident queries."""
    if params.bounds_per_partition_are_set:
        row_lo, row_hi = -np.inf, np.inf
        glo, ghi = (params.min_sum_per_partition,
                    params.max_sum_per_partition)
    elif params.bounds_per_contribution_are_set:
        row_lo, row_hi = params.min_value, params.max_value
        glo, ghi = -np.inf, np.inf
    else:
        row_lo, row_hi = -np.inf, np.inf
        glo, ghi = -np.inf, np.inf
    middle = (dp_computations.compute_middle(params.min_value,
                                             params.max_value)
              if params.bounds_per_contribution_are_set else 0.0)
    return row_lo, row_hi, glo, ghi, middle


class KeyTag(enum.IntEnum):
    """Reserved ``fold_in`` tags for the engine's PRNG substreams.

    Combiner substreams use the combiner index (0..n_combiners-1);
    QUANTILE_NOISE sits far above any realistic combiner count so the
    quantile tree's per-level noise stream can never collide with them.
    """
    QUANTILE_NOISE = 10_000


class KeyStream:
    """The audited PRNG-key source for the engine (dplint DPL001's blessed
    idiom: every key is derived exactly once and never reused).

    Two disciplines live here so key management has a single reviewed
    surface instead of ad-hoc ``fold_in`` call sites:

      * ``next_key()`` — a monotone counter folded into the root key; each
        engine-level operation (aggregate / select_partitions /
        add_dp_noise) draws one distinct key. Reproduces the historical
        ``fold_in(root_key, counter)`` sequence bit-for-bit, so seeded
        device-mode runs are unchanged across the refactor.
      * ``derive(key, tag)`` — substream derivation under a named tag
        (``KeyTag`` member or a loop index), replacing magic integers in
        ``fold_in`` calls. Deriving never consumes: the parent key remains
        valid for further ``derive`` calls with distinct tags.
    """

    def __init__(self, root_key):
        self._root_key = root_key
        self._counter = 0

    def next_key(self):
        """A fresh key, never handed out before."""
        self._counter += 1
        return jax.random.fold_in(self._root_key, self._counter)

    @property
    def counter(self) -> int:
        """How many keys have been handed out. Checkpoints and release
        tokens record this position so a resumed run under a different
        key schedule is refused (runtime/checkpoint.py)."""
        return self._counter

    def fingerprint(self) -> str:
        """Digest of the root key. (root fingerprint, counter) names the
        KeyStream state exactly — it is the release-token identity of
        runtime/journal.py, derived without consuming any key."""
        from pipelinedp_tpu.runtime import checkpoint as checkpoint_lib
        return checkpoint_lib.key_fingerprint(self._root_key)

    @staticmethod
    def derive(key, tag):
        """A substream of ``key`` under ``tag`` (see KeyTag)."""
        return jax.random.fold_in(key, int(tag))


class _LazyColumns:
    """Deferred column-dict result: computes on first access — after
    BudgetAccountant.compute_budgets(), per the lazy-budget contract
    (accessing unresolved specs raises)."""

    def __init__(self, compute_fn):
        self._compute_fn = compute_fn
        self._columns = None

    def to_columns(self) -> dict:
        """Returns {'partition_id', 'keep_mask', value arrays...}."""
        if self._columns is None:
            self._columns = self._compute_fn()
        return self._columns


class LazyJaxResult(_LazyColumns):
    """Deferred result of a columnar aggregation."""

    def __init__(self, compute_fn, pk_vocab: encoding.Vocabulary):
        super().__init__(compute_fn)
        self._pk_vocab = pk_vocab

    def to_columns(self) -> dict:
        """Returns {'partition_id', 'keep_mask', metric arrays...}
        ([num_partitions] arrays).

        Metric values of partitions dropped by partition selection are
        masked to NaN, so consuming the columns directly cannot leak
        non-kept partitions (keep_mask says which rows are real output).
        """
        return super().to_columns()

    def partition_keys(self) -> List[Any]:
        """Keys of the partitions present in the DP output (selection
        applied — non-kept partitions must not leak)."""
        cols = self.to_columns()
        keep = np.asarray(cols["keep_mask"])
        ids = np.asarray(cols["partition_id"])[keep]
        return self._pk_vocab.decode_all(ids)

    def __iter__(self):
        cols = self.to_columns()
        keep = np.asarray(cols["keep_mask"])
        kept_idx = np.flatnonzero(keep)
        # One batched vocabulary decode + one tolist per column instead of
        # a per-row decode/float() host loop.
        keys = self._pk_vocab.decode_all(
            np.asarray(cols["partition_id"])[kept_idx])
        metric_names = [
            name for name in cols
            if name not in ("partition_id", "keep_mask")
        ]
        kept_columns = []
        for name in metric_names:
            arr = np.asarray(cols[name])[kept_idx]
            kept_columns.append(arr.tolist() if arr.ndim == 1 else list(arr))
        tuple_type = combiners_lib._get_or_create_named_tuple(
            "MetricsTuple", tuple(metric_names))
        for key, *metrics in zip(keys, *kept_columns):
            yield (key, tuple_type(*metrics))


class _LazySelectedPartitions(_LazyColumns):
    """Deferred result of select_partitions: iterates kept partition keys."""

    def __init__(self, compute_fn, pk_vocab: encoding.Vocabulary):
        super().__init__(compute_fn)
        self._pk_vocab = pk_vocab

    def __iter__(self):
        cols = self.to_columns()
        ids = cols["partition_id"][cols["keep_mask"]]
        yield from self._pk_vocab.decode_all(ids)


class _LazyNoisedValues(_LazyColumns):
    """Deferred result of add_dp_noise: iterates (pk, noised value)."""

    def __init__(self, compute_fn, pk_col):
        super().__init__(compute_fn)
        self._pk_col = pk_col

    def __iter__(self):
        # Materialize both columns once (batched tolist gives native
        # Python scalars) instead of one .item()/float() per row.
        values = np.asarray(self.to_columns()["value"]).tolist()
        pk_col = self._pk_col
        if isinstance(pk_col, np.ndarray):
            pk_col = pk_col.tolist()
        yield from zip(pk_col, values)


class _LazyCustomResult(_LazyColumns):
    """Deferred result of a custom-combiner aggregation: iterates
    (partition_key, (metrics...)) like DPEngine's custom path."""

    def __init__(self, compute_fn, pk_vocab: encoding.Vocabulary):
        super().__init__(compute_fn)
        self._pk_vocab = pk_vocab

    def __iter__(self):
        cols = self.to_columns()
        for pk_id, metrics in zip(cols["partition_id"], cols["metrics"]):
            yield self._pk_vocab.decode(int(pk_id)), metrics


class JaxDPEngine:
    """Columnar DP engine. API parity with DPEngine for the aggregation
    surface; input may be Python rows (encoded on host) or pre-encoded
    columns.

    secure_host_noise: when True (default), the heavy bound-and-aggregate
    stage runs on device but the released noise (and thresholding/selection
    draws) are finalized on host in float64 with the full granularity
    snapping of noise_core — the Mironov-2012 mitigation float32 cannot
    provide (see ops/noise.py). The host step is O(num_partitions), off the
    hot path. Set False to keep everything on device (fastest; noise is
    distributionally correct but without bit-level guarantees).

    seed controls the device kernels: contribution-bounding sampling, and
    noise/selection in device mode. In secure_host_noise mode the released
    noise comes from the host secure sampler, which is deliberately NOT
    seedable through the engine (secure noise must not be replayable —
    same stance as the reference's PyDP path); tests can reseed the
    fallback RNGs via noise_core.seed_fallback_rng / partition_selection
    .seed_rng.

    mesh: a jax.sharding.Mesh with ('dp', 'mp') axes (see
    parallel.sharded.make_mesh). When set, the fused bound-and-aggregate
    kernel runs shard_map'ed over all mesh devices: rows are hash-sharded
    by privacy id on host (so contribution bounding needs no cross-device
    exchange), per-partition partials ride an ICI reduce-scatter, and the
    resulting accumulators stay sharded over the partition dimension — so
    selection and noise also run distributed under XLA's SPMD partitioner.
    Every metric and selection strategy works identically on any mesh; this
    is the framework's replacement for the reference's Beam/Spark cluster
    execution (pipeline_backend.py:223-474).
    """

    def __init__(self,
                 budget_accountant: budget_accounting.BudgetAccountant,
                 seed: int = 0,
                 secure_host_noise: bool = True,
                 mesh=None,
                 stream_chunks: Optional[int] = None,
                 value_transfer_dtype=None,
                 transfer_encoding: str = "auto",
                 compact_merge="auto",
                 segment_sort="auto",
                 fused_epilogue: bool = True,
                 epilogue_cache: Optional[finalize_ops.EpilogueCache] = None,
                 checkpoint_policy=None,
                 retry_policy=None,
                 release_journal=None,
                 fault_injector=None,
                 watchdog_timeout_s=None):
        self._budget_accountant = budget_accountant
        self._report_generators = []
        self._key_stream = KeyStream(jax.random.PRNGKey(seed))
        self._secure_host_noise = secure_host_noise
        self._mesh = mesh
        # The fused finalization epilogue (ops/finalize.py): one compiled
        # executable (device noise) or one batched host pass (secure host
        # noise) instead of a per-combiner op/sync loop. False restores
        # the legacy loop — kept as the parity oracle for tests.
        self._fused_epilogue = fused_epilogue
        # Executable cache shared across engines by default, so repeated
        # queries with the same shape hit warm epilogues with zero
        # retraces even from fresh engine instances.
        self._epilogue_cache = (epilogue_cache if epilogue_cache is not None
                                else finalize_ops.default_cache())
        # Streaming execution: large single-device inputs are hash-sharded
        # by privacy id into pid-disjoint chunks so the host->device
        # transfer overlaps the kernel (ops/streaming.py). stream_chunks=1
        # forces the single-shot path; None = auto.
        self._stream_chunks = stream_chunks
        # np.float16 halves the value-column transfer (lossy ingest,
        # opt-in; see ops/streaming.py).
        self._value_transfer_dtype = value_transfer_dtype
        # "auto": the lossless RLE/bit-plane wire codec (ops/wirecodec.py);
        # "bytes": the legacy fixed-width byte packing. Both exact.
        self._transfer_encoding = transfer_encoding
        # Compact chunk merge (ops/streaming.py): streamed chunks emit
        # compact per-group subtotal columns and ONE final merge scatters
        # them into the dense accumulators, instead of every chunk
        # re-paying the full [num_partitions] partition passes. "auto"
        # engages at >= streaming.COMPACT_MIN_PARTITIONS partitions (the
        # regime where those passes dominate); True forces it; False
        # restores the legacy per-chunk scatters (the parity oracle).
        self._compact_merge = compact_merge
        # Group-stage strategy of the streamed chunk kernels
        # (ops/columnar samplers; wirecodec.plan_group_binning resolves
        # the knob into a 4-way general/packed/tiled/hash dispatch):
        #   "auto"  — hash-binned SORTLESS group stage when it is
        #             provably bit-identical (columnar.hash_exact_gate +
        #             no norm columns), else the bucketed segment-local
        #             tiled sort when the tile heuristic wins, else the
        #             packed global sort. Bit-identical released values
        #             across all of these by construction.
        #   "hash"  — force the sortless group stage whenever its grid
        #             geometry is computable (chunks that overflow the
        #             bins demote to tiled per chunk): zero sort passes;
        #             exact counts always, sums ULP-close outside the
        #             exactness gate (bit-identical inside it).
        #   True    — force tiling whenever geometry permits.
        #   False   — the full round-8 kernel (global packed sort, f32
        #             payload, float accumulation — the parity oracle).
        self._segment_sort = segment_sort
        # Resilience knobs (pipelinedp_tpu/runtime/, RESILIENCE.md):
        #   checkpoint_policy: runtime.CheckpointPolicy — snapshot the
        #     streamed slab loop after each slab and auto-resume from the
        #     policy's store; a resumed run is bit-identical to an
        #     uninterrupted seeded run.
        #   retry_policy: runtime.RetryPolicy — bounded backoff for
        #     transient transfer/kernel failures; RESOURCE_EXHAUSTED
        #     halves the slab budget and re-issues (same per-chunk keys,
        #     so released values are unchanged).
        #   release_journal: runtime.ReleaseJournal — at-most-once noise
        #     release: a run that would re-draw already-released noise
        #     raises DoubleReleaseError instead.
        #   fault_injector: runtime.FaultInjector — deterministic fault
        #     scripting for tests (never set in production).
        #   watchdog_timeout_s: bounded timeouts around device transfer/
        #     dispatch in the streamed slab loop — a wedged operation
        #     surfaces as a retryable runtime.DispatchHangError within
        #     the timeout instead of hanging forever. None defers to
        #     PIPELINEDP_TPU_WATCHDOG_S (0 = disabled, the default).
        self._checkpoint_policy = checkpoint_policy
        self._retry_policy = retry_policy
        self._release_journal = release_journal
        self._fault_injector = fault_injector
        self._watchdog_timeout_s = watchdog_timeout_s

    def _next_key(self):
        return self._key_stream.next_key()

    # -- report plumbing (shared shape with DPEngine) -----------------------

    @property
    def _current_report_generator(self):
        return self._report_generators[-1]

    def _add_report_stage(self, stage):
        self._current_report_generator.add_stage(stage)

    def explain_computations_report(self):
        return [g.report() for g in self._report_generators]

    # -- aggregate ----------------------------------------------------------

    def aggregate(self,
                  col,
                  params: AggregateParams,
                  data_extractors: Optional[DataExtractors] = None,
                  public_partitions: Optional[Sequence[Any]] = None,
                  out_explain_computation_report: Optional[
                      ExplainComputationReport] = None) -> LazyJaxResult:
        is_columnar = (isinstance(
            col, (encoding.ColumnarData, encoding.EncodedColumns))
            or getattr(col, "is_resident_dataset", False))
        dp_engine_lib.DPEngine._check_aggregate_params(
            self, col, params, data_extractors,
            check_data_extractors=not is_columnar)
        dp_engine_lib.DPEngine._check_budget_accountant_compatibility(
            self, public_partitions is not None, params.metrics,
            params.custom_combiners is not None)
        self._check_supported(params)
        with self._budget_accountant.scope(weight=params.budget_weight):
            self._report_generators.append(
                report_generator_lib.ReportGenerator(
                    params, "aggregate", public_partitions is not None))
            if out_explain_computation_report is not None:
                out_explain_computation_report._set_report_generator(
                    self._current_report_generator)
            result = self._aggregate(col, params, data_extractors,
                                     public_partitions)
            self._budget_accountant._compute_budget_for_aggregation(
                params.budget_weight)
            return result

    # -- select_partitions / add_dp_noise (columnar fast paths) -------------

    def select_partitions(self,
                          col,
                          params: SelectPartitionsParams,
                          data_extractors: Optional[DataExtractors] = None):
        """DP-selected partition keys, computed on device.

        Columnar twin of DPEngine.select_partitions (dp_engine.py:170): one
        fused kernel L0-bounds each privacy unit's distinct partitions and
        counts distinct units per partition; one vectorized selection call
        decides the keys. Returns a lazy iterable of kept partition keys.
        """
        is_columnar = isinstance(
            col, (encoding.ColumnarData, encoding.EncodedColumns))
        if not is_columnar:
            dp_engine_lib.DPEngine._check_select_private_partitions(
                self, col, params, data_extractors)
        dp_engine_lib.DPEngine._check_budget_accountant_compatibility(
            self, False, [], False)
        with self._budget_accountant.scope(weight=params.budget_weight):
            self._report_generators.append(
                report_generator_lib.ReportGenerator(params,
                                                     "select_partitions",
                                                     False))
            spec = self._budget_accountant.request_budget(
                mechanism_type=MechanismType.GENERIC)
            pid_extractor = (None
                             if params.contribution_bounds_already_enforced
                             else (data_extractors.privacy_id_extractor
                                   if data_extractors is not None else True))
            pid, pk, _, _, pk_vocab = encoding.encode_rows(
                col,
                pid_extractor,
                data_extractors.partition_extractor
                if data_extractors else None,
                None,
                factorize_pid=False)
            num_partitions = max(len(pk_vocab), 1)
            l0 = params.max_partitions_contributed
            self._add_report_stage(
                f"Cross-partition contribution bounding: for each privacy_id "
                f"randomly select max(actual_partition_contributed, {l0}) "
                f"partitions")
            self._add_report_stage(
                lambda: f"Private partition selection: using "
                        f"{params.partition_selection_strategy.value} "
                        f"method with (eps={spec.eps}, delta={spec.delta})")
            key = self._next_key()
            key_counter = self._key_stream.counter
            engine = self

            def compute():
                engine._commit_release(key_counter, kind="selection_release")
                k_kernel, k_select = jax.random.split(key)
                counts = columnar.count_distinct_pids_per_partition(
                    jnp.asarray(pid), jnp.asarray(pk),
                    jnp.ones(len(pid), dtype=bool), k_kernel, l0,
                    num_partitions=num_partitions)
                exists = counts > 0
                strategy = ps_lib.create_partition_selection_strategy(
                    params.partition_selection_strategy, spec.eps,
                    spec.delta, l0, params.pre_threshold)
                keep, _ = engine._apply_selection(k_select, counts, exists,
                                                  strategy)
                return {
                    "partition_id":
                        np.arange(num_partitions, dtype=np.int32),
                    "keep_mask": np.asarray(keep),
                }

            result = _LazySelectedPartitions(compute, pk_vocab)
            self._budget_accountant._compute_budget_for_aggregation(
                params.budget_weight)
            return result

    def add_dp_noise(self,
                     col,
                     params,
                     out_explain_computation_report: Optional[
                         ExplainComputationReport] = None):
        """Adds calibrated DP noise to pre-aggregated (pk, value) pairs.

        Columnar twin of DPEngine.add_dp_noise (dp_engine.py:449): one
        batched noise call over the whole value column. Does NOT enforce
        sensitivity — the caller guarantees the declared l0/linf bounds
        hold and that the partition keys are public or DP-selected. Input
        is an iterable of (pk, value) pairs or a ColumnarData with
        pk/value set.
        """
        mechanism_type = params.noise_kind.convert_to_mechanism_type()
        spec = self._budget_accountant.request_budget(mechanism_type)
        sensitivities = dp_computations.Sensitivities(
            l0=params.l0_sensitivity, linf=params.linf_sensitivity)
        self._report_generators.append(
            report_generator_lib.ReportGenerator(params, "add_dp_noise",
                                                 True))
        if out_explain_computation_report is not None:
            out_explain_computation_report._set_report_generator(
                self._current_report_generator)

        if isinstance(col, encoding.ColumnarData):
            pk_col = np.asarray(col.pk)
            values = np.asarray(col.value, dtype=np.float64)
        else:
            pairs = list(col)
            pk_col = encoding._column_from_list([p for p, _ in pairs])
            values = np.array([v for _, v in pairs], dtype=np.float64)

        self._add_report_stage(
            lambda: (f"Adding {dp_computations.create_additive_mechanism(spec, sensitivities).noise_kind} "
                     f"noise with parameter "
                     f"{dp_computations.create_additive_mechanism(spec, sensitivities).noise_parameter}"))
        key = self._next_key()
        key_counter = self._key_stream.counter
        engine = self

        def compute():
            engine._commit_release(key_counter)
            is_g, scale, gran = _mechanism_noise_params(spec, sensitivities)
            # numpy in: the secure host path keeps float64 end to end; the
            # device path converts on entry.
            noised = engine._add_noise(key, values, is_g, scale, gran)
            return {
                "partition_id": np.arange(len(pk_col), dtype=np.int32),
                "keep_mask": np.ones(len(pk_col), dtype=bool),
                "value": np.asarray(noised),
            }

        result = _LazyNoisedValues(compute, pk_col)
        self._budget_accountant._compute_budget_for_aggregation(
            params.budget_weight)
        return result

    def calculate_private_contribution_bounds(
            self,
            col,
            params: CalculatePrivateContributionBoundsParams,
            data_extractors: Optional[DataExtractors] = None,
            partitions: Optional[Sequence[Any]] = None,
            partitions_already_filtered: bool = False
    ) -> PrivateContributionBounds:
        """DP choice of max_partitions_contributed via the exponential
        mechanism over dataset histograms, on the columnar path.

        Columnar twin of DPEngine.calculate_private_contribution_bounds
        (dp_engine.py:384; reference pipeline_dp/dp_engine.py:450-549):
        the L0 contribution histogram comes from the vectorized columnar
        histogram fast path (dataset_histograms/computing_histograms
        .compute_dataset_histograms_columnar) instead of a per-row
        pipeline, and the exponential-mechanism draw uses the same secure
        uniform sampler as the host engine. Supported for COUNT /
        PRIVACY_ID_COUNT aggregations.

        col: ColumnarData / EncodedColumns, or row iterable with
          data_extractors.
        partitions: the partition keys the aggregation will use (public or
          DP-selected). Required unless partitions_already_filtered and the
          number of partitions is taken from the filtered data itself.

        Returns the PrivateContributionBounds dataclass directly (the
        columnar engine has no deferred backend collections to wrap it in;
        DPEngine returns a 1-element collection with the same payload).
        """
        from pipelinedp_tpu.dataset_histograms import computing_histograms
        from pipelinedp_tpu import private_contribution_bounds as pcb_lib

        is_columnar = isinstance(
            col, (encoding.ColumnarData, encoding.EncodedColumns))
        dp_engine_lib.DPEngine.\
            _check_calculate_private_contribution_bounds_params(
                self, col, params, data_extractors,
                check_data_extractors=not is_columnar)

        if is_columnar:
            pid = np.asarray(col.pid)
            pk = np.asarray(col.pk)
        else:
            rows = list(col)
            pid = encoding._column_from_list(
                [data_extractors.privacy_id_extractor(r) for r in rows])
            pk = encoding._column_from_list(
                [data_extractors.partition_extractor(r) for r in rows])

        if partitions is not None:
            partitions = list(partitions)
            # Count the partitions from the USER-PROVIDED list before any
            # vocabulary translation: the exponential-mechanism scoring
            # must see every public partition, including keys with no data
            # (DPEngine parity — translating first silently dropped
            # unknown keys and deflated number_of_partitions).
            number_of_partitions = len(
                np.unique(encoding._column_from_list(partitions)))
            if (isinstance(col, encoding.EncodedColumns)
                    and col.pk_keys is not None):
                # EncodedColumns pk are dense ids; `partitions` arrives as
                # user-facing keys — translate through the vocabulary so
                # the filter compares ids to ids (keys absent from the
                # vocabulary cannot match any data row).
                id_of_key = {k: i for i, k in enumerate(col.pk_keys)}
                partitions = [id_of_key[p] for p in partitions
                              if p in id_of_key]
            partition_keys = np.unique(
                encoding._column_from_list(partitions))
            if not partitions_already_filtered:
                mask = np.isin(pk, partition_keys)
                pid, pk = pid[mask], pk[mask]
        elif partitions_already_filtered:
            number_of_partitions = len(np.unique(pk))
        else:
            raise ValueError(
                "partitions must be provided unless "
                "partitions_already_filtered=True")

        histograms = computing_histograms.compute_dataset_histograms_columnar(
            encoding.ColumnarData(pid=pid, pk=pk, value=None))
        scoring = pcb_lib.L0ScoringFunction(params, number_of_partitions,
                                            histograms.l0_contributions_histogram)
        candidates = pcb_lib.generate_possible_contribution_bounds(
            scoring.max_partitions_contributed_best_upper_bound())
        bound = dp_computations.ExponentialMechanism(scoring).apply(
            params.calculation_eps, candidates)
        return PrivateContributionBounds(max_partitions_contributed=bound)

    def _check_supported(self, params: AggregateParams):
        if any(m.is_percentile for m in params.metrics or []):
            if Metrics.VECTOR_SUM in params.metrics:
                raise NotImplementedError(
                    "PERCENTILE cannot be combined with VECTOR_SUM: the "
                    "quantile tree needs scalar values.")
            if params.min_value is None or params.max_value is None:
                raise ValueError(
                    "PERCENTILE requires min_value and max_value (the "
                    "quantile tree range).")
            if params.min_value >= params.max_value:
                # A zero-width tree range would produce NaN leaf indices
                # on device; fail loudly like the host quantile tree does.
                raise ValueError(
                    "PERCENTILE requires min_value < max_value (got "
                    f"[{params.min_value}, {params.max_value}]).")

    def _aggregate(self, col, params, data_extractors, public_partitions):
        resident = (col if getattr(col, "is_resident_dataset", False)
                    else None)
        if params.custom_combiners:
            if resident is not None:
                raise NotImplementedError(
                    "custom combiners are not supported on resident "
                    "dataset sessions (host combiner logic needs the raw "
                    "rows the session no longer holds)")
            return self._aggregate_custom(col, params, data_extractors,
                                          public_partitions)
        # Same budget requests as the reference graph.
        compound = combiners_lib.create_compound_combiner(
            params, self._budget_accountant)
        is_vector = Metrics.VECTOR_SUM in params.metrics
        selection_spec = None
        if (public_partitions is None and
                not params.post_aggregation_thresholding):
            selection_spec = self._budget_accountant.request_budget(
                mechanism_type=MechanismType.GENERIC)

        if resident is not None:
            # Resident-dataset fast path (pipelinedp_tpu/serving/): the
            # encode + sort + transfer phases were paid at ingest; the
            # session hands back the retained wire and the partition
            # vocabulary it was built with.
            if is_vector:
                raise NotImplementedError(
                    "VECTOR_SUM is not supported on resident dataset "
                    "sessions (the vector path has no wire codec to "
                    "retain)")
            if params.contribution_bounds_already_enforced:
                raise NotImplementedError(
                    "contribution_bounds_already_enforced re-interprets "
                    "every row as its own privacy unit, which changes the "
                    "wire; ingest the dataset that way instead")
            resident._check_engine_compat(self, public_partitions)
            pid = pk = value = None
            pk_vocab = resident.pk_vocab
            n_rows = resident.n_rows
        else:
            # Host-side columnar encoding (the extract + public-filter
            # stages). With contribution_bounds_already_enforced each row
            # is its own privacy unit and no bounding is applied (parity:
            # dp_engine.py:122). Columnar inputs carry their own pid
            # column; any non-None marker tells encode_rows to use it.
            pid_extractor = (data_extractors.privacy_id_extractor
                             if data_extractors is not None else True)
            if params.contribution_bounds_already_enforced:
                pid_extractor = None  # a unique id per row
            with profiler.stage("dp/encode"):
                pid, pk, value, pid_vocab, pk_vocab = encoding.encode_rows(
                    col,
                    pid_extractor,
                    data_extractors.partition_extractor
                    if data_extractors else None,
                    data_extractors.value_extractor
                    if data_extractors else None,
                    public_partitions=public_partitions,
                    vector_size=params.vector_size if is_vector else None,
                    factorize_pid=False)
            n_rows = len(pid)
        num_partitions = max(len(pk_vocab), 1)

        # When no child combiner expects per-partition sampling (e.g. the
        # per-partition-sum clipping mode), Linf bounding is the combiner's
        # job — disable the sampler (parity:
        # DPEngine._create_contribution_bounder, dp_engine.py:380-400).
        linf_cap, l0_cap, l1_cap = derive_contribution_caps(
            params, compound, n_rows, num_partitions)
        if params.contribution_bounds_already_enforced:
            self._add_report_stage(
                "Contribution bounding: skipped (already enforced by the "
                "caller)")
        elif l1_cap is not None:
            self._add_report_stage(
                f"Total contribution bounding: for each privacy_id randomly "
                f"select max(actual_contributions, {l1_cap}) contributions "
                f"across all partitions")
        else:
            self._add_report_stage(
                f"Per-partition contribution bounding: for each privacy_id "
                f"and each partition, randomly select max(actual_"
                f"contributions_per_partition, {linf_cap}) contributions.")
            if params.perform_cross_partition_contribution_bounding:
                self._add_report_stage(
                    f"Cross-partition contribution bounding: for each "
                    f"privacy_id randomly select max(actual_partition_"
                    f"contributed, {l0_cap}) partitions")
            else:
                self._add_report_stage(
                    "Cross-partition contribution bounding: skipped "
                    "(perform_cross_partition_contribution_bounding=False)")
        for stage in compound.explain_computation():
            self._add_report_stage(stage)

        kernel_key = self._next_key()
        key_counter = self._key_stream.counter
        engine = self

        def compute():
            nonlocal pid, pk, value
            with profiler.stage("dp/execute"):
                cols = engine._execute(compound, params, selection_spec,
                                       kernel_key, pid, pk, value,
                                       num_partitions, linf_cap, l0_cap,
                                       public_partitions is not None,
                                       is_vector, l1_cap=l1_cap,
                                       key_counter=key_counter,
                                       resident=resident)
                # The encoded columns are dead once the release exists
                # (to_columns caches it). Dropping them here, under a
                # span, puts their free (~0.8 GB at 100M rows, tens of
                # ms with the device idle) on the trace instead of in
                # the caller's code whenever it drops the result.
                with obs_trace.span("dp/free_columns"):
                    pid = pk = value = None
                return cols

        return LazyJaxResult(compute, pk_vocab)

    def _aggregate_custom(self, col, params: AggregateParams,
                          data_extractors, public_partitions):
        """Custom-combiner escape hatch (parity:
        create_compound_combiner_with_custom_combiners, reference
        combiners.py:925).

        Contribution bounding runs on the device — the fused kernel's row
        mask (columnar.bound_row_mask), identical sampling to the standard
        metrics path — and the user's combiner logic (arbitrary Python)
        runs on host over the surviving rows, grouped per (privacy_id,
        partition). Private partition selection uses the standard strategy
        over the compound accumulator's privacy-unit counts.
        """
        compound = combiners_lib.create_compound_combiner_with_custom_combiners(
            params, self._budget_accountant, params.custom_combiners)
        selection_spec = None
        if public_partitions is None:
            selection_spec = self._budget_accountant.request_budget(
                mechanism_type=MechanismType.GENERIC)

        # Host columns in float64: custom combiners receive the extracted
        # values exactly (the standard path's float32 encoding is for the
        # device kernels; user combiner logic must see what DPEngine sees).
        # Value-less pipelines (value_extractor=None / value column absent)
        # feed zeros, like DPEngine._extract_columns.
        if isinstance(col, encoding.EncodedColumns):
            # Pre-encoded dense-id columns: the float32 value column IS the
            # input format; promote it for the host combiner math.
            pid_e, pk_e, val_e, _, vocab_e = encoding.encode_rows(
                col, None if params.contribution_bounds_already_enforced
                else True, None, None, public_partitions=public_partitions)
            pid_col = (None if params.contribution_bounds_already_enforced
                       else pid_e)
            pk_col = pk_e
            value64 = np.asarray(val_e, dtype=np.float64)
            pre_encoded_vocab = vocab_e
        elif isinstance(col, encoding.ColumnarData):
            pre_encoded_vocab = None
            pid_col = (None if params.contribution_bounds_already_enforced
                       else np.asarray(col.pid))
            pk_col = np.asarray(col.pk)
            value64 = (np.zeros(len(pk_col))
                       if col.value is None else np.asarray(
                           col.value, dtype=np.float64))
        else:
            pre_encoded_vocab = None
            rows = list(col)
            pk_col = encoding._column_from_list(
                [data_extractors.partition_extractor(row) for row in rows])
            if params.contribution_bounds_already_enforced:
                pid_col = None
            else:
                pid_col = encoding._column_from_list(
                    [data_extractors.privacy_id_extractor(row)
                     for row in rows])
            if data_extractors.value_extractor is None:
                value64 = np.zeros(len(rows))
            else:
                value64 = np.asarray(
                    [data_extractors.value_extractor(row) for row in rows],
                    dtype=np.float64)
        if pre_encoded_vocab is not None:
            # encode_rows already applied the public filter and vocabulary.
            pk = pk_col
            pk_vocab = pre_encoded_vocab
        elif public_partitions is not None:
            pk_vocab = encoding.Vocabulary(list(public_partitions))
            pk = encoding._lookup_ids(pk_col, pk_vocab)
            in_public = pk >= 0
            pk = pk[in_public]
            value64 = value64[in_public]
            if pid_col is not None:
                pid_col = pid_col[in_public]
        else:
            pk, pk_uniques = encoding._factorize(pk_col)
            pk_vocab = encoding.Vocabulary.from_unique(pk_uniques)
        if pid_col is None:
            pid = np.arange(len(pk), dtype=np.int32)
        elif pre_encoded_vocab is not None:
            pid = np.asarray(pid_col, dtype=np.int32)
        else:
            pid, _ = encoding._factorize(pid_col)
        num_partitions = max(len(pk_vocab), 1)

        # Shared cap derivation with the standard path (the compound
        # gates Linf sampling; L1 mode samples per privacy unit;
        # perform_cross_partition_contribution_bounding=False disables L0
        # dropping while noise stays calibrated to the declared bound).
        linf_cap, l0_cap, l1_cap = derive_contribution_caps(
            params, compound, len(pid), num_partitions)
        if params.contribution_bounds_already_enforced:
            self._add_report_stage(
                "Contribution bounding: skipped (already enforced by the "
                "caller)")
        elif l1_cap is not None:
            self._add_report_stage(
                f"Total contribution bounding: for each privacy_id randomly "
                f"select max(actual_contributions, {l1_cap}) contributions "
                f"across all partitions")
        else:
            if compound.expects_per_partition_sampling():
                self._add_report_stage(
                    f"Per-partition contribution bounding: for each "
                    f"privacy_id and each partition, randomly select "
                    f"max(actual_contributions_per_partition, {linf_cap}) "
                    f"contributions.")
            if params.perform_cross_partition_contribution_bounding:
                self._add_report_stage(
                    f"Cross-partition contribution bounding: for each "
                    f"privacy_id randomly select max(actual_partition_"
                    f"contributed, {l0_cap}) partitions")
        if selection_spec is not None:
            self._add_report_stage(
                lambda: f"Private partition selection: using "
                        f"{params.partition_selection_strategy.value} "
                        f"method with (eps={selection_spec.eps}, "
                        f"delta={selection_spec.delta})")
        for stage in compound.explain_computation():
            self._add_report_stage(stage)
        key = self._next_key()
        key_counter = self._key_stream.counter
        engine = self

        def compute():
            engine._commit_release(key_counter)
            k_kernel, _ = jax.random.split(key)
            n_rows = len(pid)
            no_bounding = (params.contribution_bounds_already_enforced or
                           (linf_cap >= max(n_rows, 1) and
                            l0_cap >= num_partitions and l1_cap is None))
            if no_bounding or n_rows == 0:
                keep = np.ones(n_rows, dtype=bool)
            elif engine._mesh is not None:
                # Device bounding runs sharded over the mesh (pid-disjoint
                # shards, exact); the combiner loop below stays on host
                # with exact float64 values.
                from pipelinedp_tpu.parallel import sharded
                keep = sharded.host_row_mask(engine._mesh, k_kernel, pid,
                                             pk, linf_cap=linf_cap,
                                             l0_cap=l0_cap, l1_cap=l1_cap)
            else:
                keep = np.asarray(
                    columnar.bound_row_mask(k_kernel, jnp.asarray(pid),
                                            jnp.asarray(pk),
                                            jnp.ones(n_rows, dtype=bool),
                                            linf_cap, l0_cap,
                                            l1_cap=l1_cap))
            kpid, kpk, kval = pid[keep], pk[keep], value64[keep]
            # Host grouping: one lexsort, one accumulator per (pid, pk)
            # group, merged per partition (the reference's per-key
            # dataflow, collapsed).
            acc_by_pk = {}
            if len(kpid):
                order = np.lexsort((kpk, kpid))
                spid, spk, sval = kpid[order], kpk[order], kval[order]
                is_start = np.empty(len(spid), dtype=bool)
                is_start[0] = True
                np.not_equal(spid[1:], spid[:-1], out=is_start[1:])
                is_start[1:] |= spk[1:] != spk[:-1]
                starts = np.flatnonzero(is_start)
                ends = np.append(starts[1:], len(spid))
                for s, e in zip(starts, ends):
                    pk_id = int(spk[s])
                    acc = compound.create_accumulator(sval[s:e].tolist())
                    if pk_id in acc_by_pk:
                        acc = compound.merge_accumulators(
                            acc_by_pk[pk_id], acc)
                    acc_by_pk[pk_id] = acc
            if public_partitions is not None:
                # Empty public partitions release metrics too (parity:
                # DPEngine._add_empty_public_partitions).
                for pk_id in range(num_partitions):
                    if pk_id not in acc_by_pk:
                        acc_by_pk[pk_id] = compound.create_accumulator([])
                kept_ids = sorted(acc_by_pk)
            else:
                declared_l0 = (params.max_partitions_contributed
                               or params.max_contributions or 1)
                # With contribution_bounds_already_enforced each row is its
                # own encoded privacy unit: estimate true units by dividing
                # out the declared rows-per-unit bound (same adjustment as
                # the standard path / dp_engine.py).
                rows_per_unit = 1
                if params.contribution_bounds_already_enforced:
                    rows_per_unit = (params.max_contributions or
                                     params.max_contributions_per_partition)
                strategy = ps_lib.create_partition_selection_strategy(
                    params.partition_selection_strategy, selection_spec.eps,
                    selection_spec.delta, declared_l0,
                    params.pre_threshold)
                # Selection draws come from the secure sampler, not the
                # engine seed (same stance as the standard host path).
                kept_ids = sorted(
                    pk_id for pk_id, acc in acc_by_pk.items()
                    if strategy.should_keep(
                        int(np.ceil(acc[0] / rows_per_unit))))
            metrics = [
                compound.compute_metrics(acc_by_pk[pk_id])
                for pk_id in kept_ids
            ]
            return {
                "partition_id": np.asarray(kept_ids, dtype=np.int32),
                "keep_mask": np.ones(len(kept_ids), dtype=bool),
                "metrics": metrics,
            }

        return _LazyCustomResult(compute, pk_vocab)

    # -- execution (after budgets resolve) ----------------------------------

    def _execute(self, compound, params: AggregateParams, selection_spec,
                 key, pid, pk, value, num_partitions, linf_cap, l0_cap,
                 is_public: bool, is_vector: bool, l1_cap=None,
                 key_counter: int = -1, resident=None) -> dict:
        k_kernel, k_select, k_noise = jax.random.split(key, 3)
        n_rows = len(pid) if pid is not None else resident.n_rows
        has_quantile = any(
            isinstance(c, combiners_lib.QuantileCombiner)
            for c in compound.combiners)
        # Accumulators no combiner reads are never computed: each dropped
        # column saves two full-HBM segment passes in the kernel
        # (columnar.bound_and_aggregate need_* flags).
        need_flags = derive_need_flags(compound)
        # Group-level sum clipping exists only in the per-partition-bounds
        # mode; without it the kernel scatters rows straight to partitions.
        has_group_clip = bool(params.bounds_per_partition_are_set)
        row_lo, row_hi, glo, ghi, middle = derive_clip_bounds(params)

        vector_sums = None
        streamed_qhist = None
        norm_ord = {NormKind.Linf: 0, NormKind.L1: 1,
                    NormKind.L2: 2}[params.vector_norm_kind or NormKind.Linf]
        vec_sorted_kw = {}
        if is_vector:
            pid, pk, value, vec_sorted_kw = self._presort_vector_rows(
                pid, pk, value, n_rows, num_partitions, l1_cap)
        if resident is not None:
            # Resident-dataset replay: the session folds its retained
            # wire under this query's kernel key — no encode, no sort,
            # and (for device-resident handles / warm bound-cache hits)
            # no transfer or kernel either. Bit-identical to streaming
            # the source columns cold with the same key and chunk count.
            quantile_spec = None
            if has_quantile:
                if (self._mesh is not None
                        or not self._can_stream(True, num_partitions)):
                    raise NotImplementedError(
                        "PERCENTILE on a resident session needs the "
                        "streamed quantile path (single device, dense "
                        "[partitions, leaves] histogram within the "
                        "device budget)")
                quantile_spec = (
                    quantile_tree_lib.DEFAULT_BRANCHING_FACTOR
                    ** quantile_tree_lib.DEFAULT_TREE_HEIGHT,
                    params.min_value, params.max_value)
            accs = resident._accumulate(
                k_kernel,
                mesh=self._mesh,
                linf_cap=linf_cap,
                l0_cap=l0_cap,
                row_clip_lo=row_lo,
                row_clip_hi=row_hi,
                middle=middle,
                group_clip_lo=glo,
                group_clip_hi=ghi,
                l1_cap=l1_cap,
                need_flags=need_flags,
                has_group_clip=has_group_clip,
                quantile_spec=quantile_spec,
                segment_sort=self._segment_sort,
                compact_merge=self._compact_merge,
                resilience=self._stream_resilience(key_counter))
            if quantile_spec is not None:
                accs, streamed_qhist = accs
        elif self._mesh is not None:
            from pipelinedp_tpu.parallel import sharded
            if (not is_vector and not has_quantile and
                    self._stream_chunks != 1 and
                    self._transfer_encoding != "bytes" and
                    (self._stream_chunks is not None or
                     n_rows >= streaming.MIN_STREAM_ROWS)):
                # Large mesh input: chunked wire-codec ingest — each
                # chunk's sharded device_put overlaps the previous chunk's
                # kernels (parallel/sharded.stream_bound_and_aggregate).
                accs = sharded.stream_bound_and_aggregate(
                    self._mesh, k_kernel, pid, pk, value,
                    num_partitions=num_partitions,
                    linf_cap=linf_cap,
                    l0_cap=l0_cap,
                    row_clip_lo=row_lo,
                    row_clip_hi=row_hi,
                    middle=middle,
                    group_clip_lo=glo,
                    group_clip_hi=ghi,
                    l1_cap=l1_cap,
                    n_chunks=self._stream_chunks,
                    value_transfer_dtype=self._value_transfer_dtype,
                    need_flags=need_flags,
                    has_group_clip=has_group_clip,
                    resilience=self._stream_resilience(key_counter),
                    compact_merge=self._compact_merge,
                    segment_sort=self._segment_sort)
            else:
                # Stage (hash-shard + device_put) once; both the aggregate
                # and the quantile-histogram kernels reuse the staged
                # arrays.
                valid_rows = np.ones(n_rows, dtype=bool)
                pid, pk, value, valid_rows = sharded.stage_rows(
                    self._mesh, pid, pk, value, valid_rows)
                if is_vector:
                    vector_sums, accs = sharded.bound_and_aggregate_vector(
                        self._mesh, k_kernel, pid, pk, value, valid_rows,
                        num_partitions=num_partitions,
                        linf_cap=linf_cap,
                        l0_cap=l0_cap,
                        max_norm=params.vector_max_norm,
                        norm_ord=norm_ord,
                        l1_cap=l1_cap,
                        **vec_sorted_kw)
                else:
                    accs = sharded.bound_and_aggregate(
                        self._mesh, k_kernel, pid, pk, value, valid_rows,
                        num_partitions=num_partitions,
                        linf_cap=linf_cap,
                        l0_cap=l0_cap,
                        row_clip_lo=row_lo,
                        row_clip_hi=row_hi,
                        middle=middle,
                        group_clip_lo=glo,
                        group_clip_hi=ghi,
                        l1_cap=l1_cap,
                        need_flags=need_flags,
                        has_group_clip=has_group_clip)
        elif is_vector:
            vector_sums, accs = columnar.bound_and_aggregate_vector(
                k_kernel, jnp.asarray(pid), jnp.asarray(pk),
                jnp.asarray(value), jnp.ones(n_rows, dtype=bool),
                num_partitions=num_partitions,
                linf_cap=linf_cap,
                l0_cap=l0_cap,
                max_norm=params.vector_max_norm,
                norm_ord=norm_ord,
                l1_cap=l1_cap,
                **vec_sorted_kw)
        elif (self._can_stream(has_quantile, num_partitions) and
              self._stream_chunks != 1 and
              (self._stream_chunks is not None or
               n_rows >= streaming.MIN_STREAM_ROWS)):
            # Large single-device input: pid-disjoint chunked pipeline so
            # the host->device transfer overlaps the kernel and ships
            # wire-codec-compressed columns (ops/streaming.py; exact, see
            # module doc). PERCENTILE rides the same stream: quantile-tree
            # leaf counts are additive across the pid-disjoint chunks.
            quantile_spec = None
            if has_quantile:
                quantile_spec = (
                    quantile_tree_lib.DEFAULT_BRANCHING_FACTOR
                    ** quantile_tree_lib.DEFAULT_TREE_HEIGHT,
                    params.min_value, params.max_value)
            accs = streaming.stream_bound_and_aggregate(
                k_kernel, pid, pk, value,
                num_partitions=num_partitions,
                linf_cap=linf_cap,
                l0_cap=l0_cap,
                row_clip_lo=row_lo,
                row_clip_hi=row_hi,
                middle=middle,
                group_clip_lo=glo,
                group_clip_hi=ghi,
                l1_cap=l1_cap,
                n_chunks=self._stream_chunks,
                value_transfer_dtype=self._value_transfer_dtype,
                need_flags=need_flags,
                has_group_clip=has_group_clip,
                transfer_encoding=self._transfer_encoding,
                quantile_spec=quantile_spec,
                resilience=self._stream_resilience(key_counter),
                compact_merge=self._compact_merge,
                segment_sort=self._segment_sort)
            if has_quantile:
                accs, streamed_qhist = accs
        else:
            # Single-shot: the encoded columns ship as one slab, under the
            # slab driver's transfer span and wire counters.
            with obs_trace.span("dp/slab_transfer", single_shot=True):
                d_pid, d_pk, d_value = jax.device_put((pid, pk, value))
            driver_lib.count_wire(pid.nbytes + pk.nbytes + value.nbytes,
                                  n_rows)
            accs = columnar.bound_and_aggregate(
                k_kernel, d_pid, d_pk, d_value,
                jnp.ones(n_rows, dtype=bool),
                num_partitions=num_partitions,
                linf_cap=linf_cap,
                l0_cap=l0_cap,
                row_clip_lo=row_lo,
                row_clip_hi=row_hi,
                middle=middle,
                group_clip_lo=glo,
                group_clip_hi=ghi,
                l1_cap=l1_cap,
                need_count=need_flags[0],
                need_sum=need_flags[1],
                need_norm=need_flags[2],
                need_norm_sq=need_flags[3],
                has_group_clip=has_group_clip)

        # At-most-once release: the token commits BEFORE any noise is
        # drawn (the quantile noise below and the finalize epilogue), so
        # a resumed or retried run that already released under this
        # KeyStream state refuses instead of re-drawing — and a crash
        # between commit and publication errs on the side of zero
        # releases, never two (RESILIENCE.md).
        self._commit_release(key_counter)

        # On a mesh the accumulators are padded so the partition dimension
        # shards evenly; all downstream math runs on the padded arrays and
        # the final columns are trimmed back to num_partitions.
        num_out = int(accs.pid_count.shape[0])
        partition_exists = accs.pid_count > 0

        # PERCENTILE: dense [num_partitions, leaves] histograms feed every
        # partition's quantile tree at once; partition counts beyond the
        # device budget process in partition blocks over pk-sorted rows
        # (ops/quantiles.py). Computed up front so the combiner loop only
        # reads finished columns.
        quantile_cols = None
        if has_quantile:
            qcombiner = next(
                c for c in compound.combiners
                if isinstance(c, combiners_lib.QuantileCombiner))
            # k_kernel is handed out a second time on purpose: the
            # quantile path must *replay* the fused kernel's sampling
            # decisions (identical keep mask, see _quantile_columns
            # docstring), not draw an independent sample.
            # dplint: disable=DPL001 — deliberate replay of the bounding mask
            quantile_cols = self._quantile_columns(
                qcombiner, pid, pk, value, n_rows, num_out,
                num_partitions, linf_cap, l0_cap, l1_cap, k_kernel,
                KeyStream.derive(k_noise, KeyTag.QUANTILE_NOISE),
                valid_rows if self._mesh is not None else None,
                precomputed_hist=streamed_qhist)

        if self._fused_epilogue:
            return self._fused_finalize(compound, params, selection_spec,
                                        k_select, k_noise, accs, vector_sums,
                                        quantile_cols, num_partitions,
                                        is_public)
        return self._legacy_finalize(compound, params, selection_spec,
                                     k_select, k_noise, accs, vector_sums,
                                     quantile_cols, num_partitions, num_out,
                                     partition_exists, is_public)

    def _fused_finalize(self, compound, params, selection_spec, k_select,
                        k_noise, accs, vector_sums, quantile_cols,
                        num_partitions, is_public) -> dict:
        """The fused epilogue: plan construction + one dispatch + one
        batched device→host transfer (ops/finalize.py)."""
        max_rows_per_pid = 1
        if (selection_spec is not None
                and params.contribution_bounds_already_enforced):
            max_rows_per_pid = (params.max_contributions
                                or params.max_contributions_per_partition)
        plan, scalars = finalize_ops.build_plan(
            compound.combiners,
            params,
            selection_spec,
            is_public=is_public,
            num_partitions=num_partitions,
            max_rows_per_pid=max_rows_per_pid)
        t_fin0 = time.perf_counter()
        with obs_trace.span("dp/finalize",
                            secure_host_noise=self._secure_host_noise,
                            n_metrics=len(compound.combiners)) as fin_span:
            if self._secure_host_noise:
                # One batched device→host transfer of every device-resident
                # input; selection, noise and metric math then run in
                # float64 numpy with noise_core's full granularity
                # snapping.
                with profiler.stage("dp/finalize_transfer"):
                    # dplint: disable=DPL007 — secure-host-noise path: this transfer IS the mechanism boundary; host_epilogue adds float64 noise_core noise before anything is released
                    host_accs, host_vec = jax.device_get(
                        (accs, vector_sums))
                with noise_core.track_draw_threads() as noise_threads:
                    metric_cols, keep = finalize_ops.host_epilogue(
                        plan, scalars, host_accs, host_vec)
                if fin_span is not None:
                    fin_span.set_attribute("noise_threads", noise_threads[0])
            else:
                operands = finalize_ops.device_operands(
                    plan, scalars, accs, vector_sums, k_select, k_noise)
                if self._mesh is not None:
                    from pipelinedp_tpu.parallel import sharded
                    builder = functools.partial(
                        sharded.build_finalize_epilogue, self._mesh)
                else:
                    builder = None
                epilogue = self._epilogue_cache.get(plan,
                                                    self._mesh,
                                                    operands,
                                                    builder=builder)
                device_cols, device_keep = epilogue(operands)
                with profiler.stage("dp/finalize_transfer"):
                    metric_cols, keep = jax.device_get(
                        (device_cols, device_keep))
        obs_metrics.finalize_seconds().observe(
            time.perf_counter() - t_fin0)
        return finalize_ops.materialize(plan, scalars, metric_cols, keep,
                                        quantile_cols=quantile_cols)

    def _legacy_finalize(self, compound, params, selection_spec, k_select,
                         k_noise, accs, vector_sums, quantile_cols,
                         num_partitions, num_out, partition_exists,
                         is_public) -> dict:
        """The unfused per-combiner epilogue loop (fused_epilogue=False):
        one device op + blocking sync per metric. Kept as the parity
        oracle — tests/finalize_test.py pins the fused epilogue
        bit-identical to this path for seeded device-noise runs."""
        # Partition selection. The selection strategy's L0 sensitivity is
        # the *declared* cross-partition bound: max_partitions_contributed,
        # or max_contributions in L1 mode (the per-privacy-id total sample
        # of at most k rows reaches at most k partitions).
        if is_public:
            keep_mask = jnp.arange(num_out) < num_partitions
        elif selection_spec is not None:
            declared_l0 = (params.max_partitions_contributed
                           or params.max_contributions or 1)
            max_rows_per_pid = 1
            if params.contribution_bounds_already_enforced:
                max_rows_per_pid = (params.max_contributions or
                                    params.max_contributions_per_partition)
            pid_counts_est = jnp.ceil(accs.pid_count / max_rows_per_pid)
            strategy = ps_lib.create_partition_selection_strategy(
                params.partition_selection_strategy, selection_spec.eps,
                selection_spec.delta, declared_l0, params.pre_threshold)
            keep_mask, _ = self._apply_selection(k_select, pid_counts_est,
                                                 partition_exists, strategy)
        else:
            keep_mask = partition_exists  # post-agg thresholding prunes below

        # DP metrics per combiner, batched noise.
        columns = {}
        for i, combiner in enumerate(compound.combiners):
            sub_key = KeyStream.derive(k_noise, i)
            self._compute_combiner_metrics(combiner, params, accs,
                                           vector_sums, sub_key, columns,
                                           quantile_cols=quantile_cols)
            if isinstance(combiner,
                          combiners_lib.PostAggregationThresholdingCombiner):
                thresh = dp_computations.create_thresholding_mechanism(
                    combiner.mechanism_spec(), combiner.sensitivities(),
                    params.pre_threshold)
                # _compute_combiner_metrics is a no-op for the thresholding
                # combiner (handled right here), so sub_key has exactly one
                # runtime consumer on this branch.
                # dplint: disable=DPL001 — single runtime consumer per branch
                thresh_keep, noised = self._apply_selection(
                    sub_key, accs.pid_count, partition_exists,
                    thresh.strategy)
                keep_mask = keep_mask & thresh_keep
                columns["privacy_id_count"] = noised
                if params.output_noise_stddev:
                    columns["privacy_id_count_noise_stddev"] = np.full(
                        num_out, float(thresh.strategy.noise_stddev),
                        dtype=np.float64)

        # Mask metrics of non-kept partitions: direct consumers of the
        # columns must not see values partition selection dropped. Mesh
        # padding partitions are trimmed here.
        keep_np = np.asarray(keep_mask)[:num_partitions]
        for name, col in columns.items():
            arr = np.asarray(col)[:num_partitions]
            mask = keep_np if arr.ndim == 1 else keep_np[:, None]
            columns[name] = np.where(mask, arr, np.nan)
        columns["partition_id"] = np.arange(num_partitions, dtype=np.int32)
        columns["keep_mask"] = keep_np
        return columns

    def _commit_release(self, key_counter: int,
                        kind: str = "noise_release") -> None:
        """At-most-once gate for every release-producing entry point:
        commits (root fingerprint, KeyStream counter) to the engine's
        ReleaseJournal before any randomness is drawn; no-op without a
        journal (the reference's semantics — re-release is the caller's
        accounting decision)."""
        if self._release_journal is not None:
            with obs_trace.span("dp/journal_commit", kind=kind):
                self._release_journal.commit(
                    finalize_ops.release_token(
                        self._key_stream.fingerprint(), key_counter),
                    kind=kind)

    def _stream_resilience(self, key_counter: int):
        """The runtime.StreamResilience bundle for a streamed execution,
        or None when no resilience knob is set (fail-fast, zero
        overhead — the historical behavior)."""
        if (self._checkpoint_policy is None and self._retry_policy is None
                and self._fault_injector is None
                and self._watchdog_timeout_s is None):
            return None
        from pipelinedp_tpu import runtime as runtime_lib
        return runtime_lib.StreamResilience(
            retry_policy=(self._retry_policy if self._retry_policy is not None
                          else runtime_lib.RetryPolicy()),
            fault_injector=self._fault_injector,
            checkpoint_policy=self._checkpoint_policy,
            key_counter=key_counter,
            watchdog_timeout_s=self._watchdog_timeout_s)

    def _presort_vector_rows(self, pid, pk, value, n_rows: int,
                             num_partitions: int, l1_cap):
        """Host presort enabling the packed 3-key bounding sort on the
        VECTOR_SUM path -> (pid, pk, value, kernel kwargs).

        The vector path has no wire codec delivering pid-sorted rows for
        free, so a stable host argsort buys the packed 4-operand sampler
        sort (columnar.bound_and_aggregate_vector pid_sorted — vs the
        general path's 7 operands). Same sampling distribution, different
        draws than the unsorted kernel, so segment_sort=False restores
        the legacy draw-for-draw behavior. On a mesh the stable shard
        partition (shard_rows_by_pid) preserves in-shard order, so every
        device's block stays pid-sorted; the global distinct-pid count
        bounds each shard's segments. L1 mode keeps the general sampler
        (the packed layout has no L1 pre-sample), as does a packed
        layout that does not fit this shape (presorted_fits).
        """
        no_sort_kw: dict = {}
        if (self._segment_sort is False or l1_cap is not None
                or n_rows == 0 or isinstance(pid, jax.Array)):
            return pid, pk, value, no_sort_kw
        p_fit = num_partitions
        if self._mesh is not None:
            from pipelinedp_tpu.parallel import sharded
            p_fit = sharded.padded_num_partitions(self._mesh,
                                                  num_partitions)
        pid = np.asarray(pid)
        order = np.argsort(pid, kind="stable")
        spid = pid[order]
        distinct = 1 + int(np.count_nonzero(np.diff(spid)))
        max_segments = wirecodec.round_ucap(distinct)
        if not columnar.presorted_fits(n_rows, p_fit, max_segments):
            return pid, pk, value, no_sort_kw
        return (spid, np.asarray(pk)[order], np.asarray(value)[order],
                dict(pid_sorted=True, max_segments=max_segments))

    def _can_stream(self, has_quantile: bool, num_partitions: int) -> bool:
        """PERCENTILE can ride the stream when the dense [partitions,
        leaves] histogram fits the device budget (the partition-blocked
        quantile path needs pk-sorted residency, which is incompatible
        with pid-chunking) and the wire codec is in use."""
        if not has_quantile:
            return True
        if self._transfer_encoding == "bytes":
            return False
        num_leaves = (quantile_tree_lib.DEFAULT_BRANCHING_FACTOR
                      ** quantile_tree_lib.DEFAULT_TREE_HEIGHT)
        return (num_partitions * num_leaves
                <= quantile_ops.MAX_HISTOGRAM_ELEMENTS)

    # -- selection dispatch: secure host path or device kernel --------------

    def _apply_selection(self, key, counts, exists, strategy):
        """(keep_mask, noised_counts) from a host strategy object.

        The single dispatch point between the float64 secure host path
        (strategy.select_vec) and the device kernel
        (ops/selection.select_partitions) — every selection decision
        (private partition selection, post-aggregation thresholding,
        select_partitions) routes through here.
        """
        with profiler.stage("dp/partition_selection"):
            if self._secure_host_noise:
                keep, noised = strategy.select_vec(np.asarray(counts))
                return keep & np.asarray(exists), noised
            sel_params = selection_ops.selection_params_from_strategy(
                strategy)
            # Compiled entry: selection bits must not depend on whether the
            # kernel runs standalone or inlined in the fused epilogue.
            return selection_ops.select_partitions_jit(key, counts,
                                                       sel_params, exists)

    # -- noise dispatch: device kernels or float64 host finalization --------

    def _add_noise(self, key, values, is_gaussian, scale_or_std, granularity):
        with profiler.stage("dp/noise"):
            if self._secure_host_noise:
                return noise_core.add_noise_array(np.asarray(values),
                                                  bool(is_gaussian),
                                                  float(scale_or_std))
            return noise_ops.add_noise_compiled(key, jnp.asarray(values),
                                                is_gaussian, scale_or_std,
                                                granularity)

    def _add_laplace(self, key, values, scale, granularity):
        if self._secure_host_noise:
            return noise_core.add_laplace_noise_array(np.asarray(values),
                                                      float(scale))
        return noise_ops.add_laplace_noise_compiled(key, jnp.asarray(values),
                                                    scale, granularity)

    def _add_gaussian(self, key, values, stddev, granularity):
        if self._secure_host_noise:
            return noise_core.add_gaussian_noise_array(np.asarray(values),
                                                       float(stddev))
        return noise_ops.add_gaussian_noise_compiled(key,
                                                     jnp.asarray(values),
                                                     stddev, granularity)

    @staticmethod
    def _noise_stddev_column(columns: dict, name: str, is_gaussian,
                             scale_or_std, n: int) -> None:
        """[n] constant column stating the added noise's stddev (wired when
        params.output_noise_stddev — see aggregate_params.py)."""
        std = (float(scale_or_std)
               if is_gaussian else float(scale_or_std) * math.sqrt(2.0))
        columns[f"{name}_noise_stddev"] = np.full(n, std, dtype=np.float64)

    def _compute_combiner_metrics(self, combiner, params, accs, vector_sums,
                                  key, columns: dict,
                                  quantile_cols=None) -> None:
        k1, k2, k3 = jax.random.split(key, 3)
        n_out = int(np.asarray(accs.pid_count).shape[0])
        if isinstance(combiner, combiners_lib.CountCombiner):
            is_g, scale, gran = _mechanism_noise_params(
                combiner.mechanism_spec(), combiner.sensitivities())
            columns["count"] = self._add_noise(k1, accs.count, is_g, scale,
                                               gran)
            if params.output_noise_stddev:
                self._noise_stddev_column(columns, "count", is_g, scale,
                                          n_out)
        elif isinstance(combiner, combiners_lib.SumCombiner):
            is_g, scale, gran = _mechanism_noise_params(
                combiner.mechanism_spec(), combiner.sensitivities())
            columns["sum"] = self._add_noise(k1, accs.sum, is_g, scale, gran)
            if params.output_noise_stddev:
                self._noise_stddev_column(columns, "sum", is_g, scale, n_out)
        elif isinstance(combiner, combiners_lib.PrivacyIdCountCombiner):
            is_g, scale, gran = _mechanism_noise_params(
                combiner.mechanism_spec(), combiner.sensitivities())
            columns["privacy_id_count"] = self._add_noise(
                k1, accs.pid_count, is_g, scale, gran)
            if params.output_noise_stddev:
                self._noise_stddev_column(columns, "privacy_id_count", is_g,
                                          scale, n_out)
        elif isinstance(combiner,
                        combiners_lib.PostAggregationThresholdingCombiner):
            pass  # handled by the caller (needs the keep mask)
        elif isinstance(combiner, combiners_lib.MeanCombiner):
            count_spec, sum_spec = combiner.mechanism_spec()
            cg, cs, cgr = _mechanism_noise_params(
                count_spec, combiner._count_sensitivities)
            sg, ss, sgr = _mechanism_noise_params(
                sum_spec, combiner._sum_sensitivities)
            dp_count = self._add_noise(k1, accs.count, cg, cs, cgr)
            dp_norm_sum = self._add_noise(k2, accs.norm_sum, sg, ss, sgr)
            middle = dp_computations.compute_middle(params.min_value,
                                                    params.max_value)
            # np on the host path keeps the float64 width of the secure
            # noise; jnp would silently downcast to float32.
            xp = np if self._secure_host_noise else jnp
            dp_mean = middle + dp_norm_sum / xp.maximum(1.0, dp_count)
            columns["mean"] = dp_mean
            if "count" in combiner.metrics_names():
                columns["count"] = dp_count
            if "sum" in combiner.metrics_names():
                columns["sum"] = dp_mean * dp_count
        elif isinstance(combiner, combiners_lib.VarianceCombiner):
            self._variance_metrics(combiner, params, accs, (k1, k2, k3),
                                   columns)
        elif isinstance(combiner, combiners_lib.QuantileCombiner):
            # Columns precomputed by _quantile_columns (dense or blocked).
            for i, name in enumerate(combiner.metrics_names()):
                columns[name] = quantile_cols[:, i]
        elif isinstance(combiner, combiners_lib.VectorSumCombiner):
            p = combiner._params
            noise_params = p.additive_vector_noise_params
            if noise_params.noise_kind == NoiseKind.LAPLACE:
                l1 = (noise_params.l0_sensitivity *
                      noise_params.linf_sensitivity)
                scale = l1 / noise_params.eps_per_coordinate
                gran = noise_core.laplace_granularity(scale)
                columns["vector_sum"] = self._add_laplace(
                    k1, vector_sums, scale, gran)
                if params.output_noise_stddev:
                    self._noise_stddev_column(columns, "vector_sum", False,
                                              scale, n_out)
            else:
                l2 = (math.sqrt(noise_params.l0_sensitivity) *
                      noise_params.linf_sensitivity)
                sigma = noise_core.analytic_gaussian_sigma(
                    noise_params.eps_per_coordinate,
                    noise_params.delta_per_coordinate, l2)
                gran = noise_core.gaussian_granularity(sigma)
                columns["vector_sum"] = self._add_gaussian(
                    k1, vector_sums, sigma, gran)
                if params.output_noise_stddev:
                    self._noise_stddev_column(columns, "vector_sum", True,
                                              sigma, n_out)
        else:
            raise NotImplementedError(
                f"Combiner {type(combiner).__name__} is not supported on the "
                f"columnar engine.")

    def _quantile_columns(self, combiner, pid, pk, value, n_rows,
                          num_out, num_partitions, linf_cap, l0_cap, l1_cap,
                          k_kernel, k_noise, mesh_valid_rows,
                          precomputed_hist=None):
        """[num_out, n_quantiles] DP quantile estimates for every
        partition. Dense single-histogram path when the [partitions,
        leaves] layout fits the device budget; otherwise partition-blocked
        over pk-sorted rows (ops/quantiles.blocked_quantile_columns). The
        row keep mask replays the fused kernel's sampling decisions (same
        PRNG key). precomputed_hist: the [num_out, leaves] leaf histogram
        already accumulated by the streamed path (chunk-additive)."""
        p = combiner._params.aggregate_params
        eps, delta = combiner._params.eps, combiner._params.delta
        is_gaussian = p.noise_kind == NoiseKind.GAUSSIAN
        branching = quantile_tree_lib.DEFAULT_BRANCHING_FACTOR
        height = quantile_tree_lib.DEFAULT_TREE_HEIGHT
        num_leaves = branching**height
        quantiles = combiner._quantiles_to_compute
        noise_counter = [0]

        def noise_fn(levels):
            if self._secure_host_noise:
                return quantile_ops.noised_levels_host(
                    [np.asarray(lvl) for lvl in levels], eps, delta,
                    p.max_partitions_contributed,
                    p.max_contributions_per_partition, is_gaussian)
            noise_counter[0] += 1
            return quantile_ops.noised_levels_device(
                KeyStream.derive(k_noise, noise_counter[0]), levels, eps,
                delta, p.max_partitions_contributed,
                p.max_contributions_per_partition, is_gaussian)

        def finish(hist):
            # Device-noise mode keeps hist -> levels -> noise -> walk all
            # on device ([partitions, quantiles] is the only download);
            # the secure host path pulls the levels once and finishes in
            # float64 numpy. Used for the dense histogram and per block.
            levels = quantile_ops.level_counts(hist, branching, height)
            noised = noise_fn(levels)
            if self._secure_host_noise:
                return quantile_ops.walk_quantiles(noised, quantiles,
                                                   p.min_value, p.max_value,
                                                   branching)
            return np.asarray(
                quantile_ops.walk_quantiles_device(
                    noised, jnp.asarray(quantiles, dtype=jnp.float32),
                    p.min_value, p.max_value, branching=branching))

        if precomputed_hist is not None:
            return finish(precomputed_hist)
        dense_fits = num_out * num_leaves <= quantile_ops.MAX_HISTOGRAM_ELEMENTS
        if self._mesh is not None:
            from pipelinedp_tpu.parallel import sharded
            if not dense_fits:
                # Partition-blocked under the mesh: one sharded bounding
                # mask, then a sharded histogram + reduce-scatter per
                # block (sharded.blocked_quantile_columns).
                return sharded.blocked_quantile_columns(
                    self._mesh, k_kernel, pid, pk, value, mesh_valid_rows,
                    num_partitions=num_out,
                    num_leaves=num_leaves,
                    lower=p.min_value,
                    upper=p.max_value,
                    linf_cap=linf_cap,
                    l0_cap=l0_cap,
                    num_quantiles=len(quantiles),
                    finish_fn=finish,
                    l1_cap=l1_cap)
            hist = sharded.quantile_leaf_histograms(
                self._mesh, k_kernel, pid, pk, value, mesh_valid_rows,
                num_partitions=num_partitions,
                num_leaves=num_leaves,
                lower=p.min_value,
                upper=p.max_value,
                linf_cap=linf_cap,
                l0_cap=l0_cap,
                l1_cap=l1_cap)
            return finish(hist)
        row_keep = columnar.bound_row_mask(k_kernel, jnp.asarray(pid),
                                           jnp.asarray(pk),
                                           jnp.ones(n_rows, dtype=bool),
                                           linf_cap, l0_cap, l1_cap=l1_cap)
        if dense_fits:
            hist = quantile_ops.leaf_histograms(jnp.asarray(pk),
                                                jnp.asarray(value),
                                                row_keep,
                                                num_partitions=num_out,
                                                num_leaves=num_leaves,
                                                lower=p.min_value,
                                                upper=p.max_value)
            return finish(hist)
        # Blocked path: sort rows by partition on device once; each block
        # histograms a contiguous row range.
        dpk = jnp.asarray(pk)
        order = jnp.argsort(dpk)
        spk = dpk[order]
        sval = jnp.asarray(value)[order]
        skeep = row_keep[order]
        row_bounds = np.concatenate(
            [[0], np.cumsum(np.bincount(pk, minlength=num_out))])
        return quantile_ops.blocked_quantile_columns(
            spk, sval, skeep, row_bounds,
            num_partitions=num_out,
            num_leaves=num_leaves,
            lower=p.min_value,
            upper=p.max_value,
            num_quantiles=len(quantiles),
            finish_fn=finish)

    def _variance_metrics(self, combiner, params, accs, keys, columns):
        """Vectorized twin of dp_computations.compute_dp_var."""
        k1, k2, k3 = keys
        p = combiner._params
        eps, delta = p.eps, p.delta
        (b_count, b_sum, b_sq) = dp_computations.equally_split_budget(
            eps, delta, 3)
        l0 = params.max_partitions_contributed
        linf = params.max_contributions_per_partition
        noise_kind = params.noise_kind
        middle = dp_computations.compute_middle(params.min_value,
                                                params.max_value)

        def noise_arr(k, arr, eps_delta, linf_sens):
            if linf_sens == 0:
                return arr
            if noise_kind == NoiseKind.GAUSSIAN:
                sigma = noise_core.analytic_gaussian_sigma(
                    eps_delta[0], eps_delta[1],
                    dp_computations.compute_l2_sensitivity(l0, linf_sens))
                return self._add_gaussian(
                    k, arr, sigma, noise_core.gaussian_granularity(sigma))
            scale = noise_core.laplace_diversity(
                eps_delta[0],
                dp_computations.compute_l1_sensitivity(l0, linf_sens))
            return self._add_laplace(
                k, arr, scale, noise_core.laplace_granularity(scale))

        xp = np if self._secure_host_noise else jnp
        dp_count = noise_arr(k1, accs.count, b_count, linf)
        count_clamped = xp.maximum(1.0, dp_count)
        sum_linf = linf * abs(middle - params.min_value)
        dp_mean_normalized = noise_arr(k2, accs.norm_sum, b_sum,
                                       sum_linf) / count_clamped
        # Noise calibration for the sum of squares uses the squares interval
        # of the raw values (scalar twin: compute_dp_var,
        # dp_computations.py:306-365 — interval feeds sensitivity only, the
        # accumulated normalized sum of squares itself is noised as-is).
        sq_lo, sq_hi = dp_computations.compute_squares_interval(
            params.min_value, params.max_value)
        sq_middle = dp_computations.compute_middle(sq_lo, sq_hi)
        sq_linf = linf * abs(sq_middle - sq_lo)
        dp_mean_sq = noise_arr(k3, accs.norm_sq_sum, b_sq,
                               sq_linf) / count_clamped
        if self._secure_host_noise:
            dp_var = dp_mean_sq - dp_mean_normalized**2
        else:
            # Compiled: identical FMA contraction to the fused epilogue.
            dp_var = finalize_ops.variance_from_moments(dp_mean_sq,
                                                        dp_mean_normalized)
        # Parity with compute_dp_var: the middle is added only for a proper
        # range (when min == max the normalized mean is reported as-is).
        dp_mean = dp_mean_normalized + (
            middle if params.min_value != params.max_value else 0.0)
        columns["variance"] = dp_var
        if "mean" in combiner.metrics_names():
            columns["mean"] = dp_mean
        if "count" in combiner.metrics_names():
            columns["count"] = dp_count
        if "sum" in combiner.metrics_names():
            columns["sum"] = dp_mean * dp_count
