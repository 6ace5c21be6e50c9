"""Vectorized per-partition utility-analysis error models.

The TPU-first replacement for the reference's per-row combiner objects
(analysis/per_partition_combiners.py:37-451): all configurations and all
partitions are evaluated at once on a [n_configurations, n_groups] grid of
columnar pre-aggregates, reduced to [n_configurations, n_partitions]
accumulator arrays with bincount segment sums. One Python loop per
configuration never appears on the group axis.

Error model (matching the reference's combiners):
  For each (privacy_id, partition) group with contribution count c, sum s
  and privacy-id partition load m, under config with L0 bound l0:
    q = min(1, l0 / m)               # P(group survives L0 sampling)
    x = clip(v, lo, hi)              # v = s (SUM), c (COUNT), 1 (PID_COUNT)
  Per partition: raw value = sum(v), clipping errors = sum(x - v) split by
  side, E[L0 error] = -sum(x (1-q)), Var[L0 error] = sum(x^2 q (1-q)).
  Partition keep probability = E[pi(N)] where N = sum of Bernoulli(q) over
  the partition's groups (exact Poisson-binomial PGF when the partition has
  <= MAX_EXACT_PROBABILITIES privacy units, refined-normal lattice
  approximation otherwise — analysis/poisson_binomial.py:62).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from pipelinedp_tpu import budget_accounting
from pipelinedp_tpu import dp_computations
from pipelinedp_tpu import partition_selection as ps_lib
from pipelinedp_tpu.aggregate_params import (AggregateParams, MechanismType,
                                             Metric, Metrics, NoiseKind,
                                             noise_to_thresholding)
from pipelinedp_tpu.analysis import data_structures
from pipelinedp_tpu.analysis import poisson_binomial
from pipelinedp_tpu.analysis.pre_aggregation import PreAggregates

MAX_EXACT_PROBABILITIES = 100
# Lattice size of the vectorized refined-normal approximation. When the
# +-8 sigma span fits (16 sigma <= lattice), the lattice is integer and the
# result matches the scalar refined-normal PMF exactly.
_APPROX_LATTICE = 160

# The order in which metric error models are computed and reported
# (stable regardless of the order in params.metrics).
METRIC_ORDER = (Metrics.SUM, Metrics.COUNT, Metrics.PRIVACY_ID_COUNT)


@dataclasses.dataclass
class ConfigSpec:
    """One configuration of the sweep with its resolved budget split."""
    index: int
    params: AggregateParams
    selection_spec: Optional[budget_accounting.MechanismSpec]
    metric_specs: Dict[Metric, budget_accounting.MechanismSpec]
    # Private selection happens through PRIVACY_ID_COUNT thresholding
    # (no separate selection budget).
    post_agg_thresholding: bool = False


def resolve_config_budgets(options: data_structures.UtilityAnalysisOptions,
                           public_partitions: bool) -> List[ConfigSpec]:
    """Splits (epsilon, delta) per configuration.

    Each configuration gets its own accountant so different configurations
    can use different mechanisms (parity: the deep-copied accountants of
    analysis/utility_analysis_engine.py:99-143; request order selection ->
    SUM -> COUNT -> PRIVACY_ID_COUNT).
    """
    configs = []
    metrics = options.aggregate_params.metrics or []
    for i, params in enumerate(data_structures.get_aggregate_params(options)):
        accountant = budget_accounting.NaiveBudgetAccountant(
            options.epsilon, options.delta)
        post_agg = (params.post_aggregation_thresholding and
                    not public_partitions)
        if post_agg and Metrics.PRIVACY_ID_COUNT not in metrics:
            # Per-config validation: the sweep can enable the flag per
            # configuration, bypassing the engine-level check on the
            # blueprint params.
            raise ValueError(
                f"Configuration {i}: post_aggregation_thresholding requires "
                f"PRIVACY_ID_COUNT in metrics")
        selection_spec = None
        if not public_partitions and not post_agg:
            # With post-aggregation thresholding, selection rides on the
            # PRIVACY_ID_COUNT thresholding mechanism — no separate budget
            # (parity: the engine requests no GENERIC spec in that mode).
            selection_spec = accountant.request_budget(MechanismType.GENERIC)
        mechanism_type = (params.noise_kind.convert_to_mechanism_type()
                          if params.noise_kind else None)
        metric_specs = {}
        for metric in METRIC_ORDER:
            if metric in metrics:
                if metric == Metrics.PRIVACY_ID_COUNT and post_agg:
                    metric_specs[metric] = accountant.request_budget(
                        noise_to_thresholding(params.noise_kind))
                else:
                    metric_specs[metric] = accountant.request_budget(
                        mechanism_type)
        accountant.compute_budgets()
        configs.append(
            ConfigSpec(i, params, selection_spec, metric_specs,
                       post_agg_thresholding=bool(post_agg)))
    return configs


def _thresholding_strategy(
        config: ConfigSpec) -> ps_lib.PartitionSelection:
    """The post-aggregation thresholding strategy of a config (its keep
    probabilities AND its PRIVACY_ID_COUNT noise, per the engine's
    PostAggregationThresholdingCombiner)."""
    params = config.params
    spec = config.metric_specs[Metrics.PRIVACY_ID_COUNT]
    sensitivities = (
        dp_computations.compute_sensitivities_for_privacy_id_count(params))
    return dp_computations.create_thresholding_mechanism(
        spec, sensitivities, params.pre_threshold).strategy


@dataclasses.dataclass
class MetricErrorArrays:
    """[n_configs, n_partitions] error accumulators for one metric."""
    metric: Metric
    raw: np.ndarray  # non-DP per-partition value
    clip_min_err: np.ndarray
    clip_max_err: np.ndarray
    exp_l0_err: np.ndarray
    var_l0_err: np.ndarray
    std_noise: np.ndarray  # [n_configs]
    noise_kind: List[NoiseKind]  # per config


@dataclasses.dataclass
class PerPartitionArrays:
    """The complete vectorized analysis state.

    device, when set, is the analysis/device_sweep.DeviceSweep holding the
    device-resident grids; metric_errors are then lazy views that pull to
    host numpy on first array access, and the report builder
    (cross_partition.build_reports_with_histogram) reduces on-device
    without ever materializing them.
    """
    n_configs: int
    n_partitions: int
    metric_errors: List[MetricErrorArrays]
    keep_prob: Optional[np.ndarray]  # [n_configs, n_partitions]; None=public
    raw_pid_count: np.ndarray  # [n_partitions]
    raw_count: np.ndarray  # [n_partitions]
    device: Optional[object] = None

    def release_device(self, materialize: bool = True) -> None:
        """Frees the device-resident grids (see DeviceSweep.release);
        no-op for host-computed arrays."""
        if self.device is not None:
            self.device.release(materialize)
            self.device = None


def _metric_values(metric: Metric, pre: PreAggregates) -> np.ndarray:
    """Per-group raw values v of the metric (configuration-independent)."""
    if metric == Metrics.SUM:
        return pre.sums
    if metric == Metrics.COUNT:
        return pre.counts
    if metric == Metrics.PRIVACY_ID_COUNT:
        return (pre.counts > 0).astype(np.float64)
    raise ValueError(f"Unsupported analysis metric: {metric}")


def _metric_bounds(metric: Metric, params: AggregateParams):
    """(clip lo, clip hi) for the metric under one configuration
    (reference combiners: SumCombiner :244, CountCombiner :304,
    PrivacyIdCountCombiner :328)."""
    if metric == Metrics.SUM:
        if params.bounds_per_partition_are_set:
            return params.min_sum_per_partition, params.max_sum_per_partition
        # Per-contribution bounds: the engine clips each contribution to
        # [min_value, max_value] and keeps at most linf of them, so a
        # group's released sum lies in linf-scaled bounds — model
        # clipping there. DELIBERATE DEVIATION from the reference, whose
        # analysis SumCombiner reads only min/max_sum_per_partition and
        # applies NO clipping in this mode
        # (per_partition_combiners.py:250-259: np.clip with None
        # bounds); that under-reports clipping error for groups whose
        # raw sum exceeds the count-scaled bounds. Pinned by
        # tests/analysis_test.py TestSumPerContributionBounds.
        return (params.min_value * params.max_contributions_per_partition,
                params.max_value * params.max_contributions_per_partition)
    if metric == Metrics.COUNT:
        return 0.0, float(params.max_contributions_per_partition)
    if metric == Metrics.PRIVACY_ID_COUNT:
        return 0.0, 1.0
    raise ValueError(f"Unsupported analysis metric: {metric}")


def _metric_values_and_bounds(metric: Metric, pre: PreAggregates,
                              params: AggregateParams):
    """(per-group raw values v, clip lo, clip hi) for the metric under the
    given config."""
    lo, hi = _metric_bounds(metric, params)
    return _metric_values(metric, pre), lo, hi


def _segment(values: np.ndarray, pk_ids: np.ndarray,
             n_partitions: int) -> np.ndarray:
    return np.bincount(pk_ids, weights=values, minlength=n_partitions)


def _metric_noise(configs: List[ConfigSpec], metric: Metric):
    """([n_configs] noise stddevs, per-config noise kinds) — host scalar
    mechanism math, shared by the host and device grid paths."""
    std_noise = np.zeros(len(configs))
    noise_kinds = []
    for c, config in enumerate(configs):
        if (metric == Metrics.PRIVACY_ID_COUNT and
                config.post_agg_thresholding):
            # Post-aggregation thresholding: the released count is the
            # thresholding strategy's noised value.
            std_noise[c] = _thresholding_strategy(config).noise_stddev
        else:
            sensitivities = dp_computations.compute_sensitivities(
                metric, config.params)
            mechanism = dp_computations.create_additive_mechanism(
                config.metric_specs[metric], sensitivities)
            std_noise[c] = mechanism.std
        noise_kinds.append(config.params.noise_kind)
    return std_noise, noise_kinds


def compute_metric_errors(pre: PreAggregates, configs: List[ConfigSpec],
                          metric: Metric,
                          n_partitions: int) -> MetricErrorArrays:
    """Error accumulators for one metric across every configuration."""
    n_configs = len(configs)
    shape = (n_configs, n_partitions)
    raw = np.zeros(shape)
    clip_min = np.zeros(shape)
    clip_max = np.zeros(shape)
    exp_l0 = np.zeros(shape)
    var_l0 = np.zeros(shape)
    for c, config in enumerate(configs):
        params = config.params
        v, lo, hi = _metric_values_and_bounds(metric, pre, params)
        q = np.minimum(1.0, params.max_partitions_contributed /
                       np.maximum(pre.n_partitions, 1))
        x = np.clip(v, lo, hi)
        err = x - v
        raw[c] = _segment(v, pre.pk_ids, n_partitions)
        clip_min[c] = _segment(np.where(v < lo, err, 0.0), pre.pk_ids,
                               n_partitions)
        clip_max[c] = _segment(np.where(v > hi, err, 0.0), pre.pk_ids,
                               n_partitions)
        exp_l0[c] = _segment(-x * (1.0 - q), pre.pk_ids, n_partitions)
        var_l0[c] = _segment(x * x * q * (1.0 - q), pre.pk_ids, n_partitions)
    std_noise, noise_kinds = _metric_noise(configs, metric)
    return MetricErrorArrays(metric=metric,
                             raw=raw,
                             clip_min_err=clip_min,
                             clip_max_err=clip_max,
                             exp_l0_err=exp_l0,
                             var_l0_err=var_l0,
                             std_noise=std_noise,
                             noise_kind=noise_kinds)


# metric -> DeviceSweep metric_kind (analysis/device_sweep.py).
_METRIC_KIND = {
    Metrics.SUM: "sum",
    Metrics.COUNT: "count",
    Metrics.PRIVACY_ID_COUNT: "privacy_id_count",
}


def _build_device_sweep(pre: PreAggregates, configs: List[ConfigSpec],
                        ordered_metrics: List[Metric], n_partitions: int,
                        public_partitions: bool, n_units: np.ndarray,
                        mesh=None):
    """Computes the whole configuration sweep on the device.

    Returns (DeviceSweep, lazy metric_errors, approx_moments or None). The
    grids stay device-resident; LazyMetricErrorArrays materializes them to
    host numpy only when a consumer reads the arrays (the fused report
    reduction in cross_partition never does).
    """
    from pipelinedp_tpu.analysis import device_sweep

    sweep = device_sweep.DeviceSweep(pre.pk_ids, pre.counts, pre.sums,
                                     pre.n_partitions, n_partitions,
                                     len(configs), mesh=mesh)
    l0 = np.asarray(
        [config.params.max_partitions_contributed for config in configs],
        dtype=np.float64)
    kinds, los, his, stds, noise_kind_lists = [], [], [], [], []
    for metric in ordered_metrics:
        bounds = [_metric_bounds(metric, config.params) for config in configs]
        kinds.append(_METRIC_KIND[metric])
        los.append(np.asarray([b[0] for b in bounds], dtype=np.float64))
        his.append(np.asarray([b[1] for b in bounds], dtype=np.float64))
        std_noise, noise_kinds = _metric_noise(configs, metric)
        stds.append(std_noise)
        noise_kind_lists.append(noise_kinds)
    indices = sweep.add_metrics(kinds, los, his, l0, stds)
    metric_errors = [
        device_sweep.LazyMetricErrorArrays(metric, stds[m],
                                           noise_kind_lists[m], sweep,
                                           indices[m])
        for m, metric in enumerate(ordered_metrics)
    ]
    if ordered_metrics:
        # Exact (float64) per-partition sizes for report bucketing: the
        # device raw values are float32 and could land on the other side
        # of a 1-2-5 bucket boundary.
        sweep.exact_sizes = _segment(_metric_values(ordered_metrics[0], pre),
                                     pre.pk_ids, n_partitions)
    approx_moments = None
    if (not public_partitions and pre.num_groups and
            (n_units > MAX_EXACT_PROBABILITIES).any()):
        # The refined-normal keep-probability path needs the moment
        # grids on host (the strategy's pi evaluation is host math).
        sweep.compute_moments(l0)
        approx_moments = sweep.pull_moments()
    # All kernels have run: free the uploaded input columns and the
    # moments grid so only the per-metric grids stay in device memory.
    sweep.drop_inputs()
    return sweep, metric_errors, approx_moments


def _keep_prob_exact(qs: np.ndarray,
                     strategy: ps_lib.PartitionSelection) -> float:
    pmf = poisson_binomial.compute_pmf(qs)
    counts = np.arange(pmf.start, pmf.start + len(pmf.probabilities))
    return float(
        np.dot(pmf.probabilities, strategy.probability_of_keep_vec(counts)))


# Exact-path batch buckets: partitions are grouped by privacy-unit count
# and padded to the bucket upper bound (padding with q=0 units is exact —
# a Bernoulli(0) contributes nothing to the PGF), so each bucket is one
# vectorized convolution instead of a per-partition Python loop.
_EXACT_BUCKETS = (4, 8, 16, 32, 64, MAX_EXACT_PROBABILITIES)


def _keep_prob_exact_batch(q_padded: np.ndarray, shift: np.ndarray,
                           strategy: ps_lib.PartitionSelection) -> np.ndarray:
    """Exact Poisson-binomial keep probabilities for a [P, M] batch.

    Row p holds partition p's *random* (q < 1) per-unit survival
    probabilities, zero-padded; shift[p] is the partition's count of
    deterministic q == 1 units, which translate the PMF instead of being
    convolved. The PMF recurrence runs over the unit axis with all
    partitions in lockstep: pmf_{j+1} = pmf_j (1 - q_j) + shift(pmf_j) q_j
    — identical arithmetic to poisson_binomial.compute_pmf, batched.
    """
    n_rows, m = q_padded.shape
    pmf = np.zeros((n_rows, m + 1))
    pmf[:, 0] = 1.0
    shifted = np.zeros_like(pmf)
    for j in range(m):
        qj = q_padded[:, j:j + 1]
        shifted[:, 1:] = pmf[:, :-1]
        pmf = pmf * (1.0 - qj) + shifted * qj
    counts = shift[:, None] + np.arange(m + 1)[None, :]
    pok = strategy.probability_of_keep_vec(counts.ravel()).reshape(
        counts.shape)
    return np.clip((pmf * pok).sum(axis=1), 0.0, 1.0)


def _keep_prob_approx_vec(mean: np.ndarray, var: np.ndarray, m3: np.ndarray,
                          n_units: np.ndarray,
                          strategy: ps_lib.PartitionSelection) -> np.ndarray:
    """Vectorized refined-normal keep probabilities.

    For each partition, builds a lattice spanning +-8 sigma around the
    mean, computes Edgeworth-corrected CDF differences on the lattice cells
    and dots them with the strategy's keep probabilities. Integer lattices
    (16 sigma <= _APPROX_LATTICE) reproduce the scalar refined-normal PMF
    bin for bin.
    """
    from scipy import stats

    n = len(mean)
    if n == 0:
        return np.zeros(0)
    sigma = np.sqrt(var)
    sigma_safe = np.maximum(sigma, 1e-12)
    skew = np.where(sigma > 0, m3 / sigma_safe**3, 0.0)
    step = np.maximum(1.0, np.ceil(16.0 * sigma / _APPROX_LATTICE))
    start = np.maximum(0.0, np.floor(mean - 8.0 * sigma))
    k = np.arange(_APPROX_LATTICE)
    # Lattice stays unclamped: clamping ns itself would duplicate the
    # boundary cell's probability mass once per clamped point. The count at
    # which pi is evaluated is clamped instead — mass the normal
    # approximation puts beyond n_units belongs to the n_units outcome.
    ns = start[:, None] + step[:, None] * k[None, :]  # [n, K]

    def corrected_cdf(x):
        z = (x - mean[:, None]) / sigma_safe[:, None]
        g = stats.norm.cdf(z) + skew[:, None] * (1 - z * z) * stats.norm.pdf(
            z) / 6.0
        return np.clip(g, 0.0, 1.0)

    cell_prob = (corrected_cdf(ns + step[:, None] / 2.0) -
                 corrected_cdf(ns - step[:, None] / 2.0))
    counts = np.minimum(np.round(ns), n_units[:, None].astype(np.float64))
    pok = strategy.probability_of_keep_vec(
        counts.astype(np.int64).ravel()).reshape(ns.shape)
    probs = (cell_prob * pok).sum(axis=1)
    # Degenerate distributions (sigma == 0): point mass at round(mean).
    degenerate = sigma == 0
    if degenerate.any():
        point = strategy.probability_of_keep_vec(
            np.round(mean[degenerate]).astype(np.int64))
        probs[degenerate] = point
    return np.clip(probs, 0.0, 1.0)


def compute_keep_probabilities(pre: PreAggregates, configs: List[ConfigSpec],
                               n_partitions: int,
                               approx_moments: Optional[np.ndarray] = None,
                               n_units: Optional[np.ndarray] = None
                               ) -> np.ndarray:
    """[n_configs, n_partitions] private-partition keep probabilities.

    approx_moments: optional [3, n_configs, n_partitions] Poisson-binomial
    moment grids (mean, var, m3) precomputed on the device
    (device_sweep.DeviceSweep.compute_moments); when absent the moments
    are segment sums on the host. n_units: optional precomputed
    privacy-unit count per partition (one bincount pass saved on the hot
    path).
    """
    n_configs = len(configs)
    out = np.zeros((n_configs, n_partitions))
    if n_units is None:
        n_units = np.bincount(pre.pk_ids, minlength=n_partitions)
    n_units = n_units.astype(np.int64)
    # Sorted-by-partition group view, for the exact path's padded batches.
    # All of this indexing is config-independent, computed once.
    order = np.argsort(pre.pk_ids, kind="stable")
    spk = pre.pk_ids[order]
    small = np.flatnonzero(
        (n_units > 0) & (n_units <= MAX_EXACT_PROBABILITIES))
    small_set = np.zeros(n_partitions, dtype=bool)
    small_set[small] = True
    sel_small = small_set[spk]
    spk_small = spk[sel_small]
    sq_order = order[sel_small]
    # Keep probabilities depend on the config only through the selection
    # strategy and the L0 bound — NOT through linf or the sum bounds — so
    # sweep configurations differing only in those share one computation.
    cache = {}
    for c, config in enumerate(configs):
        params = config.params
        if config.post_agg_thresholding:
            # Selection = the PRIVACY_ID_COUNT thresholding strategy: the
            # analyzed strategy is exactly what the engine would run.
            strategy = _thresholding_strategy(config)
            spec = config.metric_specs[Metrics.PRIVACY_ID_COUNT]
            key = (True, spec.eps, spec.delta, params.noise_kind,
                   params.max_partitions_contributed, params.pre_threshold)
        else:
            spec = config.selection_spec
            strategy = ps_lib.create_partition_selection_strategy(
                params.partition_selection_strategy, spec.eps, spec.delta,
                params.max_partitions_contributed, params.pre_threshold)
            key = (False, spec.eps, spec.delta,
                   params.partition_selection_strategy,
                   params.max_partitions_contributed, params.pre_threshold)
        if key in cache:
            out[c] = out[cache[key]]
            continue
        cache[key] = c
        q = np.minimum(1.0, params.max_partitions_contributed /
                       np.maximum(pre.n_partitions, 1))
        if len(small):
            out[c, small] = _exact_keep_probs(q[sq_order], spk_small,
                                              n_units, small, n_partitions,
                                              strategy)
        # Vectorized refined-normal for the rest.
        big = np.flatnonzero(n_units > MAX_EXACT_PROBABILITIES)
        if len(big):
            if approx_moments is not None:
                mean = approx_moments[0, c][big]
                var = approx_moments[1, c][big]
                m3 = approx_moments[2, c][big]
            else:
                mean = _segment(q, pre.pk_ids, n_partitions)[big]
                var = _segment(q * (1 - q), pre.pk_ids, n_partitions)[big]
                m3 = _segment(q * (1 - q) * (1 - 2 * q), pre.pk_ids,
                              n_partitions)[big]
            out[c, big] = _keep_prob_approx_vec(mean, var, m3, n_units[big],
                                                strategy)
    return out


def _exact_keep_probs(sq: np.ndarray, spk_small: np.ndarray,
                      n_units: np.ndarray, small: np.ndarray,
                      n_partitions: int,
                      strategy: ps_lib.PartitionSelection) -> np.ndarray:
    """Exact keep probabilities for the small partitions (one config).

    sq: per-unit survival probabilities of the small partitions' units, in
    partition-sorted order; spk_small: their partition ids. Deterministic
    q == 1 units only translate the Poisson-binomial PMF, so partitions are
    bucketed by their count of *random* (q < 1) units — under a generous L0
    bound most units are deterministic and whole buckets collapse to a
    direct probability_of_keep lookup.
    """
    keep = np.zeros(len(small))
    is_random = sq < 1.0
    n_random = np.bincount(spk_small[is_random],
                           minlength=n_partitions)[small]
    n_all = n_units[small]
    # Fully deterministic partitions: N == n_units.
    det = n_random == 0
    if det.any():
        keep[det] = strategy.probability_of_keep_vec(n_all[det])
    # Random positions within each partition's q<1 subset.
    csel = np.flatnonzero(is_random)
    if len(csel):
        spk_r = spk_small[csel]
        starts = np.searchsorted(spk_r, spk_r, side="left")
        pos = np.arange(len(spk_r)) - starts
        # Map partition id -> row in the small/bucket arrays.
        rowmap = np.full(n_partitions, -1)
        lo = 0
        for m in _EXACT_BUCKETS:
            rows = np.flatnonzero((n_random > lo) & (n_random <= m))
            lo = m
            if not len(rows):
                continue
            rowmap[:] = -1
            rowmap[small[rows]] = np.arange(len(rows))
            in_bucket = rowmap[spk_r] >= 0
            q_padded = np.zeros((len(rows), m))
            q_padded[rowmap[spk_r[in_bucket]], pos[in_bucket]] = (
                sq[csel[in_bucket]])
            shift = n_all[rows] - n_random[rows]
            keep[rows] = _keep_prob_exact_batch(q_padded, shift, strategy)
    return keep


def compute_per_partition_arrays(pre: PreAggregates,
                                 configs: List[ConfigSpec],
                                 metrics: List[Metric],
                                 public_partitions: bool,
                                 n_partitions: Optional[int] = None,
                                 use_device: Optional[bool] = None,
                                 mesh=None) -> PerPartitionArrays:
    """Runs every error model over the whole configuration grid.

    use_device: True forces the jitted device sweep
    (analysis/device_sweep.py); False forces host numpy; None auto-selects
    (device when an accelerator is present and the grid is large). A
    device sweep that fails raises either way: it never reruns quietly on
    the host.
    mesh: a jax.sharding.Mesh to shard the sweep over (implies device).
    """
    if n_partitions is None:
        n_partitions = max(len(pre.pk_vocab), 1)
    ordered_metrics = [m for m in METRIC_ORDER if m in metrics]
    from pipelinedp_tpu.analysis import device_sweep
    if mesh is not None:
        use_device = True
    if use_device is None:
        use_device = device_sweep.should_use_device(pre.num_groups,
                                                    len(configs))
    n_units = np.bincount(pre.pk_ids, minlength=n_partitions)
    approx_moments = None
    device_state = None
    if use_device:
        device_state, metric_errors, approx_moments = _build_device_sweep(
            pre, configs, ordered_metrics, n_partitions, public_partitions,
            n_units, mesh=mesh)
    else:
        metric_errors = [
            compute_metric_errors(pre, configs, m, n_partitions)
            for m in ordered_metrics
        ]
    keep_prob = None
    if not public_partitions:
        keep_prob = compute_keep_probabilities(pre, configs, n_partitions,
                                               approx_moments=approx_moments,
                                               n_units=n_units)
    return PerPartitionArrays(
        n_configs=len(configs),
        n_partitions=n_partitions,
        metric_errors=metric_errors,
        keep_prob=keep_prob,
        raw_pid_count=n_units,
        raw_count=_segment(pre.counts, pre.pk_ids, n_partitions),
        device=device_state,
    )
