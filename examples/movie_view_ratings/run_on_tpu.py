"""DP movie-view statistics on the TPU-native columnar engine.

The flagship demo (role of the reference's
examples/movie_view_ratings/run_without_frameworks.py:101-113, re-targeted
at JaxDPEngine): COUNT, SUM, PRIVACY_ID_COUNT and rating percentiles per
movie, with private partition selection, computed as fused columnar kernels
on the accelerator.

    python run_on_tpu.py                       # synthetic data
    python run_on_tpu.py --input_file=combined_data_1.txt \
        --output_file=out.txt                  # Netflix-prize format
"""

import argparse

import numpy as np

import os
import sys

_CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, _CHECKOUT)

import pipelinedp_tpu as pdp
from pipelinedp_tpu import compile_cache

from common_utils import parse_file, synthesize_columns, write_to_file


def main():
    compile_cache.configure(_CHECKOUT)
    parser = argparse.ArgumentParser()
    parser.add_argument("--input_file", default=None,
                        help="Netflix-prize format input; synthetic if unset")
    parser.add_argument("--output_file", default=None)
    parser.add_argument("--pld_accounting", action="store_true",
                        help="PLD accounting instead of naive composition "
                             "(implies public partitions 0..99: PLD does "
                             "not support private partition selection)")
    parser.add_argument("--pre_threshold", type=int, default=None)
    parser.add_argument("--public_partitions", action="store_true",
                        help="Treat movies 0..99 as publicly known keys")
    args = parser.parse_args()

    # Load the data as columns — the TPU engine ingests columnar numpy
    # arrays directly (no per-row objects on the hot path).
    if args.input_file:
        views = parse_file(args.input_file)
        user_id = np.fromiter((v.user_id for v in views), dtype=np.int64)
        movie_id = np.fromiter((v.movie_id for v in views), dtype=np.int64)
        rating = np.fromiter((v.rating for v in views), dtype=np.int64)
    else:
        # 2k movies keeps the demo fast; the percentile metrics scale to
        # millions of movies too (the engine blocks the [movies,
        # tree-leaves] histograms over the device budget automatically).
        user_id, movie_id, rating = synthesize_columns(n_movies=2_000)
    data = pdp.ColumnarData(pid=user_id, pk=movie_id, value=rating)

    if args.pld_accounting:
        budget_accountant = pdp.PLDBudgetAccountant(total_epsilon=1,
                                                    total_delta=1e-6)
    else:
        budget_accountant = pdp.NaiveBudgetAccountant(total_epsilon=1,
                                                      total_delta=1e-6)

    engine = pdp.JaxDPEngine(budget_accountant)

    metrics = [
        pdp.Metrics.COUNT,
        pdp.Metrics.SUM,
        pdp.Metrics.PRIVACY_ID_COUNT,
    ]
    if not args.pld_accounting:
        # PLD accounting does not yet support PERCENTILE computations
        # (parity with the reference example's caveat).
        metrics.extend([
            pdp.Metrics.PERCENTILE(50),
            pdp.Metrics.PERCENTILE(90),
            pdp.Metrics.PERCENTILE(99),
        ])
    params = pdp.AggregateParams(
        metrics=metrics,
        noise_kind=pdp.NoiseKind.GAUSSIAN,
        # One user rates at most 2 movies, once each, ratings in [1, 5].
        max_partitions_contributed=2,
        max_contributions_per_partition=1,
        min_value=1,
        max_value=5)
    if args.pre_threshold:
        params.pre_threshold = args.pre_threshold

    # PLD accounting does not support private partition selection (parity
    # with the reference engine, dp_engine.py:529-531) — the reference
    # example likewise always passes public partitions.
    use_public = args.public_partitions or args.pld_accounting
    if args.pld_accounting and not args.public_partitions:
        print("note: PLD accounting requires public partitions; using "
              "movies 0..99 as publicly known keys")
    public_partitions = list(range(100)) if use_public else None

    explain_computation_report = pdp.ExplainComputationReport()
    # Lazy: the result materializes only after compute_budgets().
    dp_result = engine.aggregate(
        data,
        params,
        public_partitions=public_partitions,
        out_explain_computation_report=explain_computation_report)
    budget_accountant.compute_budgets()

    print(explain_computation_report.text())

    rows = list(dp_result)
    print(f"{len(rows)} partitions released")
    for movie, stats in rows[:5]:
        print(movie, stats)
    if args.output_file:
        write_to_file(rows, args.output_file)


if __name__ == "__main__":
    main()
