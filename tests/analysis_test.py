"""Tests for the vectorized utility-analysis layer.

Mirrors the reference's analysis/tests strategy: per-partition error
models pinned against hand-computed values, exact Poisson-binomial
cross-checks, tolerance-compared report dataclasses, and an e2e tune() on
movie-view-shaped data."""

import numpy as np
import pytest

import pipelinedp_tpu as pdp
import pipelinedp_tpu.analysis as analysis
from pipelinedp_tpu import partition_selection as ps_lib
from pipelinedp_tpu.analysis import (cross_partition, per_partition,
                                     poisson_binomial, pre_aggregation)
from pipelinedp_tpu.dataset_histograms import computing_histograms


def extractors():
    return pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                              partition_extractor=lambda r: r[1],
                              value_extractor=lambda r: r[2])


def count_params(l0=1, linf=1, **kwargs):
    return pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                               max_partitions_contributed=l0,
                               max_contributions_per_partition=linf,
                               **kwargs)


class TestPoissonBinomial:

    def test_exact_pmf_two_bernoullis(self):
        pmf = poisson_binomial.compute_pmf([0.5, 0.5])
        np.testing.assert_allclose(pmf.probabilities, [0.25, 0.5, 0.25])

    def test_exact_pmf_sums_to_one(self):
        rng = np.random.default_rng(0)
        pmf = poisson_binomial.compute_pmf(rng.uniform(0, 1, 30))
        assert pmf.probabilities.sum() == pytest.approx(1.0)

    def test_approximation_close_to_exact(self):
        rng = np.random.default_rng(1)
        probs = rng.uniform(0.3, 0.9, 80)
        exact = poisson_binomial.compute_pmf(probs)
        exp, std, skew = poisson_binomial.compute_exp_std_skewness(probs)
        approx = poisson_binomial.compute_pmf_approximation(
            exp, std, skew, len(probs))
        # Compare on the approximation's support.
        exact_slice = exact.probabilities[approx.start:approx.start +
                                          len(approx.probabilities)]
        np.testing.assert_allclose(approx.probabilities, exact_slice,
                                   atol=2e-3)


class TestPreAggregation:

    def test_groups_and_n_partitions(self):
        # user 1 -> pk a (2 contributions), pk b (1); user 2 -> pk a (1).
        rows = [(1, "a", 1.0), (1, "a", 2.0), (1, "b", 3.0), (2, "a", 4.0)]
        result = analysis.preaggregate(rows, data_extractors=extractors())
        as_dict = {}
        for pk, (count, s, n_part) in result:
            as_dict.setdefault(pk, []).append((count, s, n_part))
        assert sorted(as_dict["a"]) == [(1, 4.0, 1), (2, 3.0, 2)]
        assert as_dict["b"] == [(1, 3.0, 2)]

    def test_partition_sampling_deterministic(self):
        rows = [(u, f"pk{u % 50}", 1.0) for u in range(500)]
        r1 = analysis.preaggregate(rows, data_extractors=extractors(),
                                   partitions_sampling_prob=0.5)
        r2 = analysis.preaggregate(rows, data_extractors=extractors(),
                                   partitions_sampling_prob=0.5)
        assert [pk for pk, _ in r1] == [pk for pk, _ in r2]
        kept = {pk for pk, _ in r1}
        assert 0 < len(kept) < 50


class TestPerPartitionErrorModel:

    def _analyze(self, rows, params, eps=1.0, delta=1e-6, public=None,
                 multi=None):
        options = analysis.UtilityAnalysisOptions(
            epsilon=eps, delta=delta, aggregate_params=params,
            multi_param_configuration=multi)
        engine = analysis.UtilityAnalysisEngine()
        return engine.analyze(rows, options, extractors(),
                              public_partitions=public)

    def test_count_clipping_and_l0_errors(self):
        # One user contributes 5 rows to "a" and 1 row to "b"; linf=3, l0=1.
        rows = [(1, "a", 0.0)] * 5 + [(1, "b", 0.0)]
        result = self._analyze(rows, count_params(l0=1, linf=3),
                               public=["a", "b"])
        per_pk = dict(result)
        err_a = per_pk["a"][0].metric_errors[0]
        assert err_a.sum == 5.0
        # count 5 clipped to 3: clipping_to_max_error = -2.
        assert err_a.clipping_to_max_error == pytest.approx(-2.0)
        # q = 1/2 (2 partitions, l0=1): E[l0 err] = -3 * 0.5.
        assert err_a.expected_l0_bounding_error == pytest.approx(-1.5)
        # Var = 3^2 * 0.25.
        assert err_a.std_l0_bounding_error == pytest.approx(1.5)

    def test_count_noise_std_matches_mechanism(self):
        rows = [(1, "a", 0.0)]
        result = self._analyze(rows, count_params(l0=2, linf=3),
                               eps=2.0, delta=1e-8, public=["a"])
        err = dict(result)["a"][0].metric_errors[0]
        # All budget to COUNT (public partitions, one metric): Laplace
        # b = l0*linf/eps, std = sqrt(2) b.
        expected = np.sqrt(2.0) * 2 * 3 / 2.0
        assert err.std_noise == pytest.approx(expected)

    def test_sum_clipping(self):
        params = pdp.AggregateParams(metrics=[pdp.Metrics.SUM],
                                     max_partitions_contributed=1,
                                     max_contributions_per_partition=1,
                                     min_sum_per_partition=0.0,
                                     max_sum_per_partition=2.0)
        rows = [(1, "a", 5.0), (2, "a", -1.0)]
        result = self._analyze(rows, params, public=["a"])
        err = dict(result)["a"][0].metric_errors[0]
        assert err.sum == 4.0
        assert err.clipping_to_max_error == pytest.approx(-3.0)
        assert err.clipping_to_min_error == pytest.approx(1.0)

    def test_keep_probability_exact_matches_strategy(self):
        # 20 users, each contributing to exactly this partition (q=1):
        # the keep probability equals the strategy's probability_of_keep(20).
        rows = [(u, "a", 0.0) for u in range(20)]
        result = self._analyze(rows, count_params(), eps=1.0, delta=1e-4)
        ppm = dict(result)["a"][0]
        # Budget split: eps halved between GENERIC selection and COUNT;
        # Laplace COUNT consumes no delta, so selection gets all of it.
        strategy = ps_lib.create_partition_selection_strategy(
            pdp.PartitionSelectionStrategy.TRUNCATED_GEOMETRIC, 0.5, 1e-4, 1)
        assert ppm.partition_selection_probability_to_keep == pytest.approx(
            strategy.probability_of_keep(20), rel=1e-6)

    def test_keep_probability_approx_matches_exact(self):
        # 150 users (above the exact cutoff) with q=1: approximation must
        # agree with the exact strategy value.
        rows = [(u, "a", 0.0) for u in range(150)]
        result = self._analyze(rows, count_params(), eps=1.0, delta=1e-4)
        ppm = dict(result)["a"][0]
        strategy = ps_lib.create_partition_selection_strategy(
            pdp.PartitionSelectionStrategy.TRUNCATED_GEOMETRIC, 0.5, 5e-5, 1)
        assert ppm.partition_selection_probability_to_keep == pytest.approx(
            strategy.probability_of_keep(150), rel=1e-3)

    def test_multi_config_sweep_shapes(self):
        rows = [(u, f"pk{u % 3}", 1.0) for u in range(30)]
        multi = analysis.MultiParameterConfiguration(
            max_partitions_contributed=[1, 2, 3],
            max_contributions_per_partition=[1, 1, 2])
        result = self._analyze(rows, count_params(), multi=multi)
        arrays = result.arrays
        assert arrays.n_configs == 3
        assert arrays.metric_errors[0].raw.shape == (3, 3)
        per_config = dict(result)["pk0"]
        assert len(per_config) == 3

    def test_raw_statistics(self):
        rows = [(1, "a", 0.0), (1, "a", 0.0), (2, "a", 0.0)]
        result = self._analyze(rows, count_params(), public=["a"])
        stats = dict(result)["a"][0].raw_statistics
        assert stats.privacy_id_count == 2
        assert stats.count == 3


class TestPerformUtilityAnalysis:

    def test_public_report_averaging(self):
        # Two partitions, both kept (public): report averages per-partition
        # errors equally.
        rows = ([(u, "a", 0.0) for u in range(4)] +
                [(u + 100, "b", 0.0) for u in range(2)])
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6, aggregate_params=count_params())
        reports, per_partition_result = analysis.perform_utility_analysis(
            rows, options=options, data_extractors=extractors(),
            public_partitions=["a", "b"])
        assert len(reports) == 1
        report = reports[0]
        assert report.partitions_info.public_partitions
        assert report.partitions_info.num_dataset_partitions == 2
        err = report.metric_errors[0]
        # No clipping/l0 error (l0=1 but each user contributes to exactly 1
        # partition): bias 0, variance = noise^2, rmse = noise std.
        assert err.absolute_error.mean == pytest.approx(0.0)
        assert err.absolute_error.rmse == pytest.approx(err.noise_std)
        # ((pk, config), PerPartitionMetrics) entries: 2 partitions x 1 cfg.
        assert len(per_partition_result) == 2

    def test_private_report_weighted_by_keep_prob(self):
        rows = ([(u, "big", 0.0) for u in range(1000)] +
                [(1, "small", 0.0)])
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-4, aggregate_params=count_params())
        reports, _ = analysis.perform_utility_analysis(
            rows, options=options, data_extractors=extractors())
        info = reports[0].partitions_info
        assert not info.public_partitions
        assert info.num_dataset_partitions == 2
        # big is kept ~surely, small ~never.
        assert info.kept_partitions.mean == pytest.approx(1.0, abs=0.05)
        assert info.strategy is not None

    def test_histogram_buckets(self):
        sizes = np.array([0, 1, 5, 10, 20, 50, 100, 999])
        buckets = cross_partition.partition_size_buckets(sizes)
        assert list(buckets) == [0, 1, 1, 10, 20, 50, 100, 500]
        assert cross_partition.bucket_upper_bound(10) == 20


class TestDPStrategySelector:

    def test_gaussian_wins_for_large_l0(self):
        selector = analysis.DPStrategySelector(
            epsilon=1.0, delta=1e-6, metric=pdp.Metrics.COUNT,
            is_public_partitions=True)
        import pipelinedp_tpu.dp_computations as dp_computations
        strategy = selector.get_dp_strategy(
            dp_computations.Sensitivities(l0=100, linf=1))
        assert strategy.noise_kind == pdp.NoiseKind.GAUSSIAN

    def test_laplace_wins_for_small_l0(self):
        selector = analysis.DPStrategySelector(
            epsilon=1.0, delta=1e-6, metric=pdp.Metrics.COUNT,
            is_public_partitions=True)
        import pipelinedp_tpu.dp_computations as dp_computations
        strategy = selector.get_dp_strategy(
            dp_computations.Sensitivities(l0=1, linf=1))
        assert strategy.noise_kind == pdp.NoiseKind.LAPLACE

    def test_privacy_id_count_uses_post_aggregation_thresholding(self):
        selector = analysis.DPStrategySelector(
            epsilon=1.0, delta=1e-6, metric=pdp.Metrics.PRIVACY_ID_COUNT,
            is_public_partitions=False)
        import pipelinedp_tpu.dp_computations as dp_computations
        strategy = selector.get_dp_strategy(
            dp_computations.Sensitivities(l0=10, linf=1))
        assert strategy.post_aggregation_thresholding
        assert strategy.partition_selection_strategy is not None

    def test_select_partitions_case(self):
        selector = analysis.DPStrategySelector(epsilon=1.0, delta=1e-6,
                                               metric=None,
                                               is_public_partitions=False)
        import pipelinedp_tpu.dp_computations as dp_computations
        strategy = selector.get_dp_strategy(
            dp_computations.Sensitivities(l0=5, linf=1))
        assert strategy.noise_kind is None
        assert strategy.partition_selection_strategy is not None


class TestTune:

    def _movie_shaped_rows(self, n_users=400, n_movies=40, seed=0):
        rng = np.random.default_rng(seed)
        rows = []
        for u in range(n_users):
            n_watched = 1 + rng.integers(0, 8)
            movies = rng.choice(n_movies, size=min(n_watched, n_movies),
                                replace=False)
            for m in movies:
                rows.append((u, int(m), float(rng.integers(1, 6))))
        return rows

    def test_tune_count_returns_rmse_ranked_result(self):
        rows = self._movie_shaped_rows()
        histograms = list(computing_histograms.compute_dataset_histograms(
            rows, extractors(), pdp.LocalBackend()))[0]
        options = analysis.TuneOptions(
            epsilon=1.0,
            delta=1e-6,
            aggregate_params=count_params(l0=1, linf=1),
            function_to_minimize=analysis.MinimizingFunction.ABSOLUTE_ERROR,
            parameters_to_tune=analysis.ParametersToTune(
                max_partitions_contributed=True,
                max_contributions_per_partition=True),
            number_of_parameter_candidates=16)
        result, per_partition_result = analysis.tune(
            rows, contribution_histograms=histograms, options=options,
            data_extractors=extractors())
        assert isinstance(result, analysis.TuneResult)
        candidates = result.utility_analysis_parameters
        assert candidates.size <= 16
        assert len(result.utility_reports) == candidates.size
        assert 0 <= result.index_best < candidates.size
        # Reports carry RMSE; best really is the argmin.
        rmse = [r.metric_errors[0].absolute_error.rmse
                for r in result.utility_reports]
        assert result.index_best == int(np.argmin(rmse))
        # Strategies were attached per candidate.
        assert len(candidates.noise_kind) == candidates.size
        assert len(candidates.partition_selection_strategy) == candidates.size
        assert per_partition_result

    def test_tune_sum(self):
        rows = self._movie_shaped_rows()
        histograms = list(computing_histograms.compute_dataset_histograms(
            rows, extractors(), pdp.LocalBackend()))[0]
        params = pdp.AggregateParams(metrics=[pdp.Metrics.SUM],
                                     max_partitions_contributed=1,
                                     max_contributions_per_partition=1,
                                     min_sum_per_partition=0.0,
                                     max_sum_per_partition=1.0)
        options = analysis.TuneOptions(
            epsilon=1.0,
            delta=1e-6,
            aggregate_params=params,
            function_to_minimize=analysis.MinimizingFunction.ABSOLUTE_ERROR,
            parameters_to_tune=analysis.ParametersToTune(
                max_partitions_contributed=True,
                max_sum_per_partition=True),
            number_of_parameter_candidates=9)
        result, _ = analysis.tune(rows, contribution_histograms=histograms,
                                  options=options,
                                  data_extractors=extractors())
        assert result.index_best >= 0
        best = result.utility_analysis_parameters.get_aggregate_params(
            params, result.index_best)
        assert best.max_sum_per_partition > 0

    def test_tune_rejects_two_metrics(self):
        options_kwargs = dict(
            epsilon=1.0, delta=1e-6,
            function_to_minimize=analysis.MinimizingFunction.ABSOLUTE_ERROR,
            parameters_to_tune=analysis.ParametersToTune(
                max_partitions_contributed=True))
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=1,
            max_contributions_per_partition=1,
            min_value=0, max_value=1)
        with pytest.raises(ValueError, match="one metric"):
            analysis.tune(
                [], contribution_histograms=None,
                options=analysis.TuneOptions(aggregate_params=params,
                                             **options_kwargs),
                data_extractors=extractors())


class TestCandidateGeneration:

    def test_constant_relative_step(self):
        from pipelinedp_tpu.dataset_histograms import histograms as h
        bins = [h.FrequencyBin(1, 2, 10, 5, 1), h.FrequencyBin(
            99, 100, 3, 1, 100)]
        hist = h.Histogram(h.HistogramType.L0_CONTRIBUTIONS, bins)
        candidates = analysis.parameter_tuning.\
            candidates_constant_relative_step(hist, 5)
        assert candidates[0] == 1
        assert candidates[-1] == 100
        assert candidates == sorted(set(candidates))

    def test_2d_grid_size(self):
        from pipelinedp_tpu.analysis.parameter_tuning import candidates_2d_grid
        fn = lambda hist, k: list(range(1, k + 1))
        g1, g2 = candidates_2d_grid(None, None, fn, fn, 16)
        assert len(g1) == len(g2) == 16


class TestDatasetSummary:

    def test_overlap_counts(self):
        rows = [(1, "a", 0.0), (2, "b", 0.0), (3, "c", 0.0)]
        summary = analysis.compute_public_partitions_summary(
            rows, extractors=extractors(),
            public_partitions=["a", "b", "zzz"])
        assert summary.num_dataset_public_partitions == 2
        assert summary.num_dataset_non_public_partitions == 1
        assert summary.num_empty_public_partitions == 1


class TestMultiParameterConfiguration:

    def test_size_validation(self):
        with pytest.raises(ValueError, match="same length"):
            analysis.MultiParameterConfiguration(
                max_partitions_contributed=[1, 2],
                max_contributions_per_partition=[1])

    def test_get_aggregate_params(self):
        config = analysis.MultiParameterConfiguration(
            max_partitions_contributed=[1, 5],
            noise_kind=[pdp.NoiseKind.LAPLACE, pdp.NoiseKind.GAUSSIAN])
        params = config.get_aggregate_params(count_params(), 1)
        assert params.max_partitions_contributed == 5
        assert params.noise_kind == pdp.NoiseKind.GAUSSIAN


class TestPostAggregationThresholdingAnalysis:
    """Verdict-r2 task 8: the analysis models post-aggregation thresholding
    so the tuner can honor the strategy selector's PRIVACY_ID_COUNT
    recommendation."""

    def _pid_params(self, post_agg):
        return pdp.AggregateParams(
            metrics=[pdp.Metrics.PRIVACY_ID_COUNT],
            noise_kind=pdp.NoiseKind.GAUSSIAN,
            max_partitions_contributed=1,
            max_contributions_per_partition=1,
            post_aggregation_thresholding=post_agg)

    def test_keep_prob_matches_thresholding_strategy(self):
        # 40 users, all in one partition, each contributing once: N is
        # deterministic, so the modeled keep probability must equal the
        # thresholding strategy's probability_of_keep(40) exactly.
        rows = [(u, "p", 1.0) for u in range(40)]
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6,
            aggregate_params=self._pid_params(True))
        engine = analysis.UtilityAnalysisEngine()
        result = engine.analyze(rows, options, extractors())
        keep_prob = result.arrays.keep_prob[0, 0]
        configs = per_partition.resolve_config_budgets(options, False)
        assert configs[0].post_agg_thresholding
        strategy = per_partition._thresholding_strategy(configs[0])
        assert keep_prob == pytest.approx(strategy.probability_of_keep(40),
                                          abs=1e-9)
        # The modeled noise std is the thresholding strategy's noise.
        pid_errors = [
            e for e in result.arrays.metric_errors
            if e.metric == pdp.Metrics.PRIVACY_ID_COUNT
        ][0]
        assert pid_errors.std_noise[0] == pytest.approx(
            strategy.noise_stddev)

    def test_thresholding_gets_full_budget(self):
        # Without post-agg thresholding the budget is split between
        # selection and noise; with it, the thresholding mechanism gets
        # everything — its noise must be strictly smaller.
        rows = [(u, u % 3, 1.0) for u in range(60)]
        def std_of(post_agg):
            options = analysis.UtilityAnalysisOptions(
                epsilon=1.0, delta=1e-6,
                aggregate_params=self._pid_params(post_agg))
            engine = analysis.UtilityAnalysisEngine()
            result = engine.analyze(rows, options, extractors())
            return [
                e for e in result.arrays.metric_errors
                if e.metric == pdp.Metrics.PRIVACY_ID_COUNT
            ][0].std_noise[0]
        assert std_of(True) < std_of(False)

    def test_tune_privacy_id_count_analyzes_selector_strategy(self):
        # The selector recommends post-aggregation thresholding for
        # PRIVACY_ID_COUNT; tune() must attach and analyze that bit
        # instead of dropping it.
        rng = np.random.default_rng(0)
        rows = [(int(u), int(rng.integers(0, 20)), 1.0)
                for u in range(500)]
        hists = list(
            computing_histograms.compute_dataset_histograms(
                rows, extractors(), pdp.LocalBackend()))[0]
        options = analysis.TuneOptions(
            epsilon=1.0,
            delta=1e-6,
            aggregate_params=self._pid_params(False),
            function_to_minimize=analysis.MinimizingFunction.ABSOLUTE_ERROR,
            parameters_to_tune=analysis.ParametersToTune(
                max_partitions_contributed=True),
            number_of_parameter_candidates=5)
        tune_result, _ = analysis.tune(rows,
                                       contribution_histograms=hists,
                                       options=options,
                                       data_extractors=extractors())
        candidates = tune_result.utility_analysis_parameters
        assert candidates.post_aggregation_thresholding is not None
        assert all(candidates.post_aggregation_thresholding)
        assert 0 <= tune_result.index_best < candidates.size


class TestVectorizedExactKeepProbabilities:
    """Verdict-r2 task 4: the exact Poisson-binomial path is batched, with
    exactness pinned against the scalar PGF and approx agreement pinned at
    the exact/approx boundary."""

    def _pre_and_config(self, rows, l0=2):
        from pipelinedp_tpu.analysis import pre_aggregation
        ext = extractors()
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                                     noise_kind=pdp.NoiseKind.LAPLACE,
                                     max_partitions_contributed=l0,
                                     max_contributions_per_partition=2)
        options = analysis.UtilityAnalysisOptions(epsilon=1.0, delta=1e-6,
                                                  aggregate_params=params)
        pre = pre_aggregation.preaggregate_from_rows(rows, ext)
        configs = per_partition.resolve_config_budgets(options, False)
        return pre, configs, params

    def test_batch_matches_scalar_exact(self):
        rng = np.random.default_rng(3)
        rows = []
        for p in range(60):
            for u in range(int(rng.integers(1, 40))):
                uid = p * 1000 + u
                rows.append((uid, p, 1.0))
                # Vary each user's partition load so q < 1 varies.
                for extra in range(int(rng.integers(0, 4))):
                    rows.append((uid, 500 + extra, 1.0))
        pre, configs, params = self._pre_and_config(rows)
        n_partitions = max(len(pre.pk_vocab), 1)
        out = per_partition.compute_keep_probabilities(
            pre, configs, n_partitions)
        spec = configs[0].selection_spec
        strategy = ps_lib.create_partition_selection_strategy(
            params.partition_selection_strategy, spec.eps, spec.delta,
            params.max_partitions_contributed, None)
        q = np.minimum(
            1.0, params.max_partitions_contributed /
            np.maximum(pre.n_partitions, 1))
        order = np.argsort(pre.pk_ids, kind="stable")
        spk = pre.pk_ids[order]
        bounds = np.searchsorted(spk, np.arange(n_partitions + 1))
        for p in range(n_partitions):
            qs = q[order[bounds[p]:bounds[p + 1]]]
            if not len(qs) or len(qs) > per_partition.MAX_EXACT_PROBABILITIES:
                continue
            ref = per_partition._keep_prob_exact(qs, strategy)
            assert out[0, p] == pytest.approx(ref, abs=1e-12), p

    def test_exact_and_approx_agree_at_boundary(self):
        # Two partitions straddling MAX_EXACT_PROBABILITIES with identical
        # per-unit survival probabilities: the exact PGF (n=100) and the
        # refined-normal lattice (n=101) must agree closely.
        m = per_partition.MAX_EXACT_PROBABILITIES
        rows = []
        for u in range(m):
            rows.append((u, "exact", 1.0))
            rows.append((u, "other_a", 1.0))  # load 3 -> q = 2/3
            rows.append((u, "other_b", 1.0))
        for u in range(m + 1):
            uid = 10_000 + u
            rows.append((uid, "approx", 1.0))
            rows.append((uid, "other_a", 1.0))
            rows.append((uid, "other_b", 1.0))
        pre, configs, params = self._pre_and_config(rows, l0=2)
        n_partitions = max(len(pre.pk_vocab), 1)
        out = per_partition.compute_keep_probabilities(
            pre, configs, n_partitions)
        keys = pre.pk_vocab.keys
        p_exact = out[0, keys.index("exact")]
        p_approx = out[0, keys.index("approx")]
        # n differs by one unit; both ~ kept with the same probability.
        assert p_approx == pytest.approx(p_exact, abs=0.01)
        assert 0 < p_exact < 1


class TestSumPerContributionBounds:
    """Verdict-r2 task 10b: SUM analysis under per-contribution bounds.

    Pinned semantics: the error model clips each (pid, partition) group's
    sum at count-scaled bounds [min_value*linf, max_value*linf] — what the
    engine's per-contribution clipping + Linf sampling actually bounds.
    (Deliberate deviation from the reference, whose analysis SumCombiner
    applies no clipping in this mode; see per_partition.py.)"""

    def _params(self, linf=2):
        return pdp.AggregateParams(metrics=[pdp.Metrics.SUM],
                                   noise_kind=pdp.NoiseKind.LAPLACE,
                                   max_partitions_contributed=1,
                                   max_contributions_per_partition=linf,
                                   min_value=0.0,
                                   max_value=3.0)

    def _analyze(self, rows):
        options = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6, aggregate_params=self._params())
        engine = analysis.UtilityAnalysisEngine()
        return engine.analyze(rows, options, extractors(),
                              public_partitions=["a"])

    def test_clipping_at_count_scaled_bounds(self):
        # One user, 4 contributions of 3.0 to "a": raw group sum 12;
        # count-scaled cap = max_value * linf = 6 -> clip error -6.
        rows = [(1, "a", 3.0)] * 4
        result = self._analyze(rows)
        err = dict(result)["a"][0].metric_errors[0]
        assert err.sum == pytest.approx(12.0)
        assert err.clipping_to_max_error == pytest.approx(-6.0)
        assert err.clipping_to_min_error == pytest.approx(0.0)

    def test_no_clipping_within_bounds(self):
        rows = [(1, "a", 2.0), (1, "a", 1.0)]  # sum 3 <= 6
        result = self._analyze(rows)
        err = dict(result)["a"][0].metric_errors[0]
        assert err.clipping_to_max_error == pytest.approx(0.0)
        assert err.clipping_to_min_error == pytest.approx(0.0)

    def test_noise_std_uses_per_contribution_sensitivity(self):
        rows = [(1, "a", 1.0)]
        result = self._analyze(rows)
        err = dict(result)["a"][0].metric_errors[0]
        # Public partitions, one metric: full eps to SUM. Laplace scale =
        # l0 * linf * max_abs / eps = 1*2*3/1.
        assert err.std_noise == pytest.approx(np.sqrt(2.0) * 6.0)


class TestDeviceSweep:
    """Conformance of the jitted device sweep (analysis/device_sweep.py)
    against the host numpy error model (VERDICT-r3 task 1): the two paths
    must agree on every [n_configs, n_partitions] grid."""

    def _random_rows(self, n_users=80, n_partitions=7, rows_per_user=6,
                     seed=7):
        rng = np.random.default_rng(seed)
        rows = []
        for u in range(n_users):
            for _ in range(rng.integers(1, rows_per_user + 1)):
                pk = f"pk{rng.integers(0, n_partitions)}"
                rows.append((u, pk, float(rng.normal(2.0, 3.0))))
        return rows

    def _options(self, public, use_device, post_agg=False, mesh=None):
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM,
                     pdp.Metrics.PRIVACY_ID_COUNT],
            noise_kind=pdp.NoiseKind.GAUSSIAN,
            max_partitions_contributed=2,
            max_contributions_per_partition=3,
            min_sum_per_partition=0.0,
            max_sum_per_partition=5.0,
            post_aggregation_thresholding=post_agg)
        multi = analysis.MultiParameterConfiguration(
            max_partitions_contributed=[1, 2, 3, 5],
            max_contributions_per_partition=[1, 2, 3, 4],
            min_sum_per_partition=[0.0, -1.0, 0.0, -2.0],
            max_sum_per_partition=[2.0, 5.0, 10.0, 3.0])
        return analysis.UtilityAnalysisOptions(
            epsilon=2.0, delta=1e-5, aggregate_params=params,
            multi_param_configuration=multi, use_device_sweep=use_device,
            device_mesh=mesh)

    def _arrays(self, rows, public, use_device, post_agg=False, mesh=None):
        engine = analysis.UtilityAnalysisEngine()
        result = engine.analyze(
            rows,
            self._options(public is not None, use_device, post_agg, mesh),
            extractors(), public_partitions=public)
        return result.arrays

    def _make_mesh(self):
        import jax
        from pipelinedp_tpu.parallel import sharded
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        return sharded.make_mesh(8)

    def _assert_grids_match(self, host, dev):
        assert dev.n_configs == host.n_configs
        assert dev.n_partitions == host.n_partitions
        for he, de in zip(host.metric_errors, dev.metric_errors):
            assert de.metric == he.metric
            for field in ("raw", "clip_min_err", "clip_max_err",
                          "exp_l0_err", "var_l0_err"):
                np.testing.assert_allclose(getattr(de, field),
                                           getattr(he, field),
                                           rtol=1e-4, atol=1e-5,
                                           err_msg=f"{he.metric} {field}")
            np.testing.assert_allclose(de.std_noise, he.std_noise)
        if host.keep_prob is None:
            assert dev.keep_prob is None
        else:
            np.testing.assert_allclose(dev.keep_prob, host.keep_prob,
                                       rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(dev.raw_pid_count, host.raw_pid_count)
        np.testing.assert_allclose(dev.raw_count, host.raw_count)

    def test_device_matches_host_public(self):
        rows = self._random_rows()
        public = [f"pk{i}" for i in range(9)]  # incl. 2 empty partitions
        host = self._arrays(rows, public, use_device=False)
        dev = self._arrays(rows, public, use_device=True)
        self._assert_grids_match(host, dev)

    def test_device_matches_host_private_selection(self):
        rows = self._random_rows()
        host = self._arrays(rows, None, use_device=False)
        dev = self._arrays(rows, None, use_device=True)
        self._assert_grids_match(host, dev)

    def test_device_moments_drive_refined_normal_path(self):
        # One partition with 150 users (above MAX_EXACT_PROBABILITIES) so
        # the keep probability rides the approximate path, whose moments
        # come from the device kernel when the sweep is on-device.
        rows = [(u, "big", 1.0) for u in range(150)]
        rows += [(u, f"pk{u % 3}", 1.0) for u in range(30)]
        host = self._arrays(rows, None, use_device=False)
        dev = self._arrays(rows, None, use_device=True)
        self._assert_grids_match(host, dev)

    def test_device_matches_host_post_aggregation_thresholding(self):
        rows = self._random_rows(n_users=40)
        host = self._arrays(rows, None, use_device=False, post_agg=True)
        dev = self._arrays(rows, None, use_device=True, post_agg=True)
        self._assert_grids_match(host, dev)

    def test_empty_dataset(self):
        host = self._arrays([], ["pk0"], use_device=False)
        dev = self._arrays([], ["pk0"], use_device=True)
        self._assert_grids_match(host, dev)

    def test_auto_dispatch_is_host_on_cpu(self):
        from pipelinedp_tpu.analysis import device_sweep
        # The test environment is a CPU mesh: auto must not engage.
        assert not device_sweep.should_use_device(1 << 22, 64)

    def test_auto_dispatched_device_failure_propagates(self, monkeypatch):
        # An auto-selected device sweep that fails must raise, not rerun
        # quietly on the host.
        from pipelinedp_tpu.analysis import device_sweep
        from pipelinedp_tpu.analysis import per_partition

        def fail(*args, **kwargs):
            raise RuntimeError("injected device sweep failure")

        monkeypatch.setattr(device_sweep, "should_use_device",
                            lambda *args: True)
        monkeypatch.setattr(per_partition, "_build_device_sweep", fail)
        with pytest.raises(RuntimeError, match="injected"):
            self._arrays(self._random_rows(), None, use_device=None)

    # -- mesh sweep (VERDICT-r4 item 2): mesh == single-device == host ----

    def test_mesh_matches_host_and_single_device_public(self):
        mesh = self._make_mesh()
        rows = self._random_rows()
        public = [f"pk{i}" for i in range(9)]
        host = self._arrays(rows, public, use_device=False)
        dev = self._arrays(rows, public, use_device=True)
        mesh_arrays = self._arrays(rows, public, use_device=True, mesh=mesh)
        self._assert_grids_match(host, mesh_arrays)
        self._assert_grids_match(dev, mesh_arrays)

    def test_mesh_matches_host_private_selection(self):
        mesh = self._make_mesh()
        rows = self._random_rows()
        host = self._arrays(rows, None, use_device=False)
        mesh_arrays = self._arrays(rows, None, use_device=True, mesh=mesh)
        self._assert_grids_match(host, mesh_arrays)

    def test_mesh_moments_refined_normal(self):
        mesh = self._make_mesh()
        rows = [(u, "big", 1.0) for u in range(150)]
        rows += [(u, f"pk{u % 3}", 1.0) for u in range(30)]
        host = self._arrays(rows, None, use_device=False)
        mesh_arrays = self._arrays(rows, None, use_device=True, mesh=mesh)
        self._assert_grids_match(host, mesh_arrays)

    def test_mesh_report_reduction_matches_host(self):
        # The fused report reduction through build_reports_with_histogram
        # on the mesh: shard-local bucket sums + psum must reproduce the
        # host reports.
        mesh = self._make_mesh()
        rows = self._random_rows(n_users=50, n_partitions=10)
        public = [f"pk{i}" for i in range(10)]
        options_host = self._options(True, False)
        options_mesh = self._options(True, True, mesh=mesh)
        host_reports, _ = analysis.perform_utility_analysis(
            rows, options=options_host, data_extractors=extractors(),
            public_partitions=public)
        mesh_reports, _ = analysis.perform_utility_analysis(
            rows, options=options_mesh, data_extractors=extractors(),
            public_partitions=public)
        assert len(host_reports) == len(mesh_reports)
        for h, m in zip(host_reports, mesh_reports):
            _assert_dataclass_close(h, m, rtol=1e-3, atol=1e-4)


def _assert_dataclass_close(a, b, path="", rtol=1e-4, atol=1e-6):
    import dataclasses as _dc
    assert type(a) is type(b), f"{path}: {type(a)} vs {type(b)}"
    if _dc.is_dataclass(a):
        for f in _dc.fields(a):
            _assert_dataclass_close(getattr(a, f.name), getattr(b, f.name),
                                    f"{path}.{f.name}", rtol, atol)
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), f"{path}: len {len(a)} vs {len(b)}"
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_dataclass_close(x, y, f"{path}[{i}]", rtol, atol)
    elif isinstance(a, float):
        assert b == pytest.approx(a, rel=rtol, abs=atol), f"{path}: {a} vs {b}"
    else:
        assert a == b, f"{path}: {a} vs {b}"


class TestDeviceReportReduction:
    """The fused on-device cross-partition report reduction
    (cross_partition._build_reports_device) must reproduce the host report
    builder field for field."""

    def _reports(self, rows, public, use_device):
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM,
                     pdp.Metrics.PRIVACY_ID_COUNT],
            noise_kind=pdp.NoiseKind.LAPLACE,
            max_partitions_contributed=2,
            max_contributions_per_partition=3,
            min_sum_per_partition=0.0,
            max_sum_per_partition=5.0)
        multi = analysis.MultiParameterConfiguration(
            max_partitions_contributed=[1, 2, 4],
            max_contributions_per_partition=[1, 2, 3])
        options = analysis.UtilityAnalysisOptions(
            epsilon=2.0, delta=1e-5, aggregate_params=params,
            multi_param_configuration=multi, use_device_sweep=use_device)
        return analysis.perform_utility_analysis(
            rows, options=options, data_extractors=extractors(),
            public_partitions=public)

    def _rows(self):
        rng = np.random.default_rng(3)
        rows = []
        for u in range(60):
            for _ in range(rng.integers(1, 6)):
                rows.append((u, f"pk{rng.integers(0, 12)}",
                             float(rng.normal(2.0, 2.0))))
        # A large partition so size buckets span several decades.
        rows += [(1000 + u, "huge", 1.0) for u in range(400)]
        return rows

    def test_public_reports_match(self):
        rows = self._rows()
        public = [f"pk{i}" for i in range(14)] + ["huge"]  # 2 empty
        host_reports, _ = self._reports(rows, public, use_device=False)
        dev_reports, _ = self._reports(rows, public, use_device=True)
        _assert_dataclass_close(host_reports, dev_reports)

    def test_private_reports_match(self):
        rows = self._rows()
        host_reports, host_pp = self._reports(rows, None, use_device=False)
        dev_reports, dev_pp = self._reports(rows, None, use_device=True)
        _assert_dataclass_close(host_reports, dev_reports)
        # The lazy per-partition rows materialize consistently too.
        assert len(dev_pp) == len(host_pp)
        _assert_dataclass_close(host_pp[0][1], dev_pp[0][1])

    def test_tune_runs_on_device_sweep(self):
        # parameter_tuning consumes only reports: the device path must
        # carry a full tune() end-to-end.
        rows = self._rows()
        data_extractors = extractors()
        hist = list(computing_histograms.compute_dataset_histograms(
            rows, data_extractors, pdp.LocalBackend()))[0]
        options = analysis.TuneOptions(
            epsilon=2.0, delta=1e-5,
            aggregate_params=count_params(l0=2, linf=2),
            function_to_minimize=analysis.MinimizingFunction.ABSOLUTE_ERROR,
            parameters_to_tune=analysis.ParametersToTune(
                max_partitions_contributed=True,
                max_contributions_per_partition=True),
            number_of_parameter_candidates=8,
            use_device_sweep=True)
        result, _ = analysis.tune(rows, contribution_histograms=hist,
                                  options=options,
                                  data_extractors=data_extractors)
        assert result.utility_reports
        rmse = [r.metric_errors[0].absolute_error.rmse
                for r in result.utility_reports]
        assert result.index_best == int(np.argmin(rmse))

    def test_release_device_after_materialize(self):
        # Access through the lazy per-partition rows after releasing the
        # device grids with materialization: still works.
        rows = self._rows()
        engine = analysis.UtilityAnalysisEngine()
        opts = analysis.UtilityAnalysisOptions(
            epsilon=1.0, delta=1e-6, aggregate_params=count_params(l0=2),
            use_device_sweep=True)
        result = engine.analyze(rows, opts, extractors())
        result.arrays.release_device(materialize=True)
        assert result.arrays.device is None
        first = next(iter(result))
        assert first[1][0].metric_errors
