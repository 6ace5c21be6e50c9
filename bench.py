"""Benchmark: DP-aggregated partitions/sec (COUNT+SUM) on the columnar
TPU engine vs the LocalBackend CPU oracle.

Headline config (BASELINE.md): synthetic movie_view_ratings-shaped workload,
100M rows / 1M partitions, COUNT+SUM per partition, Laplace noise, private
partition selection, eps=1 delta=1e-6, max_partitions_contributed=8.

Two measurements:
  * e2e — the full public API path: JaxDPEngine.aggregate on raw host
    columns (ColumnarData), including dictionary encoding, host->device
    transfer, the fused kernel, private partition selection, and the secure
    float64 host noise finalization. This is what a user gets.
  * kernel — the fused device step alone on resident data (the sustained
    throughput once data lives on device, e.g. inside a larger pipeline).

The CPU baseline runs DPEngine+LocalBackend on a smaller sample of the same
shape (rows-per-partition held constant) and its partitions/sec is used
directly — LocalBackend cost is linear in rows == partitions * density, so
partitions/sec at equal density is scale-free.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import sys
import time

import numpy as np

N_ROWS = int(os.environ.get("BENCH_ROWS", 100_000_000))
N_PARTITIONS = int(os.environ.get("BENCH_PARTITIONS", 1_000_000))
N_USERS = max(N_ROWS // 10, 1)
L0_CAP = 8
LINF_CAP = 4
EPS, DELTA = 1.0, 1e-6

# 2M rows / 20k partitions: big enough that the partitions/sec extrapolation
# to the 100M-row workload rests on a 50x smaller gap (LocalBackend cost is
# linear in rows; density held equal), small enough to finish in ~30 s.
CPU_ROWS = int(os.environ.get("BENCH_CPU_ROWS", 2_000_000))
CPU_PARTITIONS = max(CPU_ROWS * N_PARTITIONS // N_ROWS, 1)


def _trace_dir() -> str:
    """Where per-row Chrome trace files land (BENCH_TRACE_DIR, default
    a bench-traces dir under the system tmp)."""
    import tempfile
    path = os.environ.get("BENCH_TRACE_DIR")
    if not path:
        path = os.path.join(tempfile.gettempdir(), "pdp_bench_traces")
    os.makedirs(path, exist_ok=True)
    return path


def _traced_run(label: str, fn):
    """One EXTRA (untimed) execution of ``fn`` under a fresh tracer;
    returns the written Chrome-trace path. Separate from the timed runs
    so the published numbers stay tracing-free — the trace documents the
    span structure of the row, not its timing."""
    from pipelinedp_tpu.obs import trace as obs_trace

    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        fn()
        return tracer.write_chrome(
            os.path.join(_trace_dir(), f"{label}.json"))
    finally:
        obs_trace.shutdown()


def _host_columns(seed=0):
    """Zipf-skewed partition popularity (movie-view-shaped): head partitions
    clear the private-selection threshold, the long tail is dropped.

    Values are integer star ratings 1..5 — the reference's north-star
    workload aggregates the Netflix-prize rating column, which is integer
    stars (/root/reference/examples/movie_view_ratings/
    run_without_frameworks.py). The wire codec's continuous-value (raw
    float32) path is exercised separately in tests/wirecodec_test.py."""
    rng = np.random.default_rng(seed)
    pk = (N_PARTITIONS * rng.random(N_ROWS)**4).astype(np.int32)
    return (rng.integers(0, N_USERS, N_ROWS, dtype=np.int32),
            np.minimum(pk, N_PARTITIONS - 1),
            rng.integers(1, 6, N_ROWS).astype(np.float32))


def _params():
    import pipelinedp_tpu as pdp
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        max_partitions_contributed=L0_CAP,
        max_contributions_per_partition=LINF_CAP,
        min_value=0.0,
        max_value=5.0)


def bench_e2e(pid, pk, value, n_runs=3, segment_sort="auto"):
    """Full public-API path on raw host columns.

    Returns (partitions_per_sec, phases) where phases is the per-stage
    host wall-second budget of the fastest run (profiler stage times).
    Host encode phases (dp/wire_prep, dp/wire_sort, dp/stream_slab_*) are
    HOST time; device transfer+kernels dispatched inside them run async,
    so the sync stages (dp/partition_selection) absorb whatever the
    device had left — that split is the overlap evidence.
    """
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import profiler

    from pipelinedp_tpu.ops import streaming

    scatter_keys = (streaming.EVENT_PARTITION_SCATTERS,
                    streaming.EVENT_COMPACT_MERGE_SCATTERS,
                    streaming.EVENT_COMPACT_CHUNKS)

    def run(seed):
        before = {k: profiler.event_count(k) for k in scatter_keys}
        with profiler.collect_stage_times() as stages:
            t0 = time.perf_counter()
            data = pdp.ColumnarData(pid=pid, pk=pk, value=value)
            accountant = pdp.NaiveBudgetAccountant(EPS, DELTA)
            engine = pdp.JaxDPEngine(accountant, seed=seed,
                                     segment_sort=segment_sort)
            result = engine.aggregate(data, _params())
            accountant.compute_budgets()
            cols = result.to_columns()
            n_kept = int(np.asarray(cols["keep_mask"]).sum())
            assert n_kept > 0
            elapsed = time.perf_counter() - t0
        stages = dict(stages)
        # Executed scatter-pass counts for THIS aggregate (the structural
        # evidence of the compact merge: row-scale partition passes per
        # chunk -> compact merge passes per aggregate).
        for k in scatter_keys:
            stages["#" + k] = profiler.event_count(k) - before[k]
        return elapsed, stages

    run(100)  # warmup/compile
    # min-of-n: the fastest run is the sustained capability of the path;
    # the spread between runs on a PCIe-attached chip is not measured yet.
    results = [run(i) for i in range(n_runs)]
    best_s, best_stages = min(results, key=lambda r: r[0])
    phases = _coarse_phases(best_stages, best_s)
    try:
        phases["trace_file"] = _traced_run("e2e", lambda: run(200))
    except Exception as e:  # noqa: BLE001 — tracing never fails the row
        phases["trace_error"] = f"{type(e).__name__}: {e}"[:120]
    return N_PARTITIONS / best_s, phases


def _coarse_phases(stages: dict, e2e_s: float) -> dict:
    """Folds raw stage names into the phase budget the bench publishes."""
    slab_host = sum(v for k, v in stages.items()
                    if k.startswith("dp/stream_slab_"))
    sort_piped = stages.get("dp/wire_sort", 0.0)
    sort_upfront = stages.get("dp/wire_sort_upfront", 0.0)
    phases = {
        "e2e_s": round(e2e_s, 3),
        "encode_s": round(stages.get("dp/encode", 0.0), 3),
        "wire_prep_s": round(stages.get("dp/wire_prep", 0.0), 3),
        # Host radix sort inside the slab pipeline (overlapped with the
        # previous slab's transfer + kernels) vs serialized up front.
        "wire_sort_pipelined_s": round(sort_piped, 3),
        "wire_sort_upfront_s": round(sort_upfront, 3),
        # Host seconds the lookahead prefetcher spent encoding upcoming
        # slabs on background threads (sort+emit fully overlapped with
        # the in-flight window's transfer + kernels).
        "wire_sort_parallel_s": round(
            stages.get("dp/wire_sort_parallel", 0.0), 3),
        # Host side of the slab loop: sort (nested) + emit + async puts +
        # kernel dispatch.
        "stream_host_s": round(slab_host, 3),
        # Sync points: whatever device work the pipeline didn't hide.
        "selection_sync_s": round(stages.get("dp/partition_selection",
                                             0.0), 3),
        "noise_s": round(stages.get("dp/noise", 0.0), 3),
        # Fused epilogue (ops/finalize.py): the whole post-aggregation
        # path in one dispatch; finalize_transfer is the single batched
        # device->host sync that replaced the per-metric np.asarray tail.
        "finalize_s": round(stages.get("dp/finalize", 0.0), 3),
        "finalize_transfer_s": round(stages.get("dp/finalize_transfer",
                                                0.0), 3),
    }
    phases["host_encode_overlapped"] = bool(
        sort_upfront == 0.0 and slab_host > 0.0)
    # Executed scatter-pass counters (see bench_e2e.run): legacy pays
    # row-scale partition scatters per chunk; the compact merge pays
    # compact-input merge scatters once per aggregate.
    from pipelinedp_tpu.ops import streaming
    phases["partition_scatter_passes"] = int(
        stages.get("#" + streaming.EVENT_PARTITION_SCATTERS, 0))
    phases["compact_merge_scatter_passes"] = int(
        stages.get("#" + streaming.EVENT_COMPACT_MERGE_SCATTERS, 0))
    phases["compact_chunks"] = int(
        stages.get("#" + streaming.EVENT_COMPACT_CHUNKS, 0))
    return phases


def bench_e2e_steady(pid, pk, value, n_calls=4, secure_host_noise=True):
    """Warm-cache steady state: n_calls repeated `aggregate` calls of the
    same query shape, each through a FRESH engine/accountant (executables
    are cached process-wide). Separates compile amortization from kernel
    gains: the first call pays every trace, steady-state calls must pay
    zero (per-call epilogue trace counts are reported to prove it).
    """
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu.ops import finalize

    times, traces = [], []
    for i in range(n_calls):
        traces_before = finalize.trace_count()
        t0 = time.perf_counter()
        data = pdp.ColumnarData(pid=pid, pk=pk, value=value)
        accountant = pdp.NaiveBudgetAccountant(EPS, DELTA)
        engine = pdp.JaxDPEngine(accountant, seed=i,
                                 secure_host_noise=secure_host_noise)
        result = engine.aggregate(data, _params())
        accountant.compute_budgets()
        cols = result.to_columns()
        assert int(np.asarray(cols["keep_mask"]).sum()) > 0
        times.append(time.perf_counter() - t0)
        traces.append(finalize.trace_count() - traces_before)
    cache = finalize.default_cache()
    return {
        "first_call_partitions_per_sec": round(N_PARTITIONS / times[0], 1),
        "steady_state_partitions_per_sec": round(
            N_PARTITIONS / min(times[1:]), 1),
        "per_call_epilogue_traces": traces,
        "epilogue_cache_hits": cache.hits,
        "epilogue_cache_misses": cache.misses,
    }


def bench_kernel(pid, pk, value) -> dict:
    """Fused device step on resident data (sustained throughput).

    Four group-stage configurations of the same bounding kernel A/B the
    round-10 tentpole on resident columns:
      * general — unsorted rows, 4-key/7-operand sort (the historical
        kernel-resident row since round 1, kept for trajectory
        continuity: this is the ~305k/s floor the tentpole targets);
      * packed — rows pre-sorted by pid on host (untimed prep — the
        streamed wire delivers this order for free), packed 3-key global
        sort with the float32 value payload (the wire-ingest kernel of
        rounds 6-8, segment_sort=False);
      * tiled — the same packed keys over bucketed segment-local tiles
        with the narrow value payload and int32 group accumulation
        (rounds 9's default, segment_sort=True);
      * hash — the SORTLESS hash-binned group stage (round 10,
        segment_sort="hash"; the auto default for this COUNT+SUM shape
        under the exactness gate): one scatter into per-segment bins,
        keyed-priority selection, zero sort passes over the wire.
        Bit-identical sampling (and, under the gate, bit-identical
        releases) to packed/tiled.

    Returns {partitions_per_sec (headline = hash, the auto default at
    this shape), *_partitions_per_sec per config, sort: per-config
    columnar.sort_cost rows + reduction ratios + the hash grid's
    occupancy, and modeled_vs_measured_sort_bytes — the statically
    summed model vs the bytes actually credited to the ops/sort_*
    counters during the timed runs (ratio 1.0 = the counter story is
    honest)}; costs are credited to the profiler counters exactly as
    the streaming drivers do per executed chunk.
    """
    import jax
    import jax.numpy as jnp

    from pipelinedp_tpu import profiler
    from pipelinedp_tpu.ops import columnar, noise as noise_ops
    from pipelinedp_tpu.ops import selection as selection_ops
    from pipelinedp_tpu.ops import wirecodec
    from pipelinedp_tpu import partition_selection as ps_lib
    from pipelinedp_tpu import noise_core

    host_strategy = ps_lib.TruncatedGeometricPartitionSelection(
        EPS / 3, DELTA, L0_CAP)
    sp = selection_ops.selection_params_from_strategy(host_strategy)
    # eps split: 1/3 each to selection, count, sum (NaiveBudgetAccountant
    # semantics for COUNT+SUM+selection).
    count_scale = L0_CAP * LINF_CAP / (EPS / 3)
    sum_scale = L0_CAP * LINF_CAP * 5.0 / (EPS / 3)

    def make_step(**kernel_kwargs):
        @jax.jit
        def step(key, pid, pk, value):
            valid = jnp.ones(N_ROWS, dtype=bool)
            accs = columnar.bound_and_aggregate(
                key, pid, pk, value, valid,
                num_partitions=N_PARTITIONS,
                linf_cap=LINF_CAP, l0_cap=L0_CAP,
                row_clip_lo=0.0, row_clip_hi=5.0, middle=2.5,
                group_clip_lo=-jnp.inf, group_clip_hi=jnp.inf,
                need_norm=False, need_norm_sq=False, has_group_clip=False,
                **kernel_kwargs)
            k_sel, k_c, k_s = jax.random.split(jax.random.fold_in(key, 1),
                                               3)
            keep, _ = selection_ops.select_partitions(
                k_sel, accs.pid_count, sp, accs.pid_count > 0)
            dp_count = noise_ops.add_noise(
                k_c, accs.count, False, count_scale,
                noise_core.laplace_granularity(count_scale))
            dp_sum = noise_ops.add_noise(
                k_s, accs.sum, False, sum_scale,
                noise_core.laplace_granularity(sum_scale))
            return dp_count, dp_sum, keep

        return step

    def force(x):
        # device_get of a scalar reduction guarantees the computation ran
        # to completion even on platforms where block_until_ready is lax.
        return float(jax.device_get(jnp.sum(x[0]) + jnp.sum(x[1])))

    def measure(step, columns, cost):
        key = jax.random.PRNGKey(0)
        dev = [jax.device_put(c) for c in columns]
        jax.block_until_ready(dev)
        force(step(jax.random.fold_in(key, 100), *dev))  # warmup/compile
        times = []
        for i in range(3):
            t0 = time.perf_counter()
            force(step(jax.random.fold_in(key, i), *dev))
            times.append(time.perf_counter() - t0)
            profiler.count_event(columnar.EVENT_SORT_ROWS, cost["rows"])
            profiler.count_event(columnar.EVENT_SORT_TILES, cost["tiles"])
            profiler.count_event(columnar.EVENT_SORT_BYTES,
                                 cost["operand_bytes"])
        return N_PARTITIONS / min(times)

    # Host prep for the pid-sorted configs (untimed: the streamed wire
    # delivers pid-sorted buckets as a by-product of its host encode).
    order = np.argsort(pid, kind="stable")
    spid, spk, svalue = pid[order], pk[order], value[order]
    per_pid = np.bincount(spid - spid.min())
    max_run = int(per_pid.max())
    max_segments = wirecodec.round_ucap(int((per_pid > 0).sum()))
    tile_slack = -(-max_run // 8) * 8
    tile_rows = 1 << max(10, (4 * max_run - 1).bit_length())
    # Hash-bin grid (round 10): one bin per pid segment, width = the max
    # single-pid run rounded up — the same prep-time stats the wire's
    # plan_group_binning sizes from.
    hash_bin_rows = max(8, (max_run + 7) & ~7)
    hash_bins = max_segments
    # Narrow value payload: star ratings 1..5 are their own plane index
    # (lo=0, scale=1, 3 bits) — the same affine-grid contract the wire
    # codec's VALUE_PLANES mode ships.
    int_clip = columnar.int_accumulation_plan(0.0, 1.0, 3, 0.0, 5.0,
                                              LINF_CAP)

    sort_kw = dict(num_partitions=N_PARTITIONS, max_segments=max_segments,
                   pid_sorted=True)
    costs = {
        "general": columnar.sort_cost(N_ROWS,
                                      num_partitions=N_PARTITIONS),
        "packed": columnar.sort_cost(N_ROWS, **sort_kw),
        "tiled": columnar.sort_cost(N_ROWS, tile_rows=tile_rows,
                                    tile_slack=tile_slack, value_bytes=1,
                                    **sort_kw),
        "hash": columnar.sort_cost(N_ROWS, hash_bins=hash_bins,
                                   hash_bin_rows=hash_bin_rows,
                                   value_bytes=1, **sort_kw),
    }
    out = {"sort": {name: dict(c) for name, c in costs.items()}}
    out["sort"]["tiled_vs_packed_operand_byte_reduction"] = round(
        1.0 - costs["tiled"]["operand_bytes"]
        / max(costs["packed"]["operand_bytes"], 1), 3)
    out["sort"]["tiled_vs_general_operand_byte_reduction"] = round(
        1.0 - costs["tiled"]["operand_bytes"]
        / max(costs["general"]["operand_bytes"], 1), 3)
    # The sortless group stage: zero sort operand bytes by construction.
    out["sort"]["hash_sort_operand_bytes"] = costs["hash"]["operand_bytes"]
    out["sort"]["hash_bin_occupancy_pct"] = round(
        100.0 * N_ROWS / max(hash_bins * hash_bin_rows, 1), 1)

    bytes_before = profiler.event_count(columnar.EVENT_SORT_BYTES)
    out["general_partitions_per_sec"] = round(
        measure(make_step(), [pid, pk, value], costs["general"]), 1)
    packed_kw = dict(pid_sorted=True, max_segments=max_segments)
    out["packed_partitions_per_sec"] = round(
        measure(make_step(**packed_kw), [spid, spk, svalue],
                costs["packed"]), 1)
    tiled_kw = dict(tile_rows=tile_rows, tile_slack=tile_slack,
                    value_is_index=True, value_lo=0.0, value_scale=1.0,
                    value_sort_bits=3, **packed_kw)
    if int_clip is not None:
        tiled_kw.update(int_accumulate=True, int_clip_lo=int_clip[0],
                        int_clip_hi=int_clip[1])
    out["tiled_partitions_per_sec"] = round(
        measure(make_step(**tiled_kw),
                [spid, spk, svalue.astype(np.int32)], costs["tiled"]), 1)
    hash_kw = dict(hash_bins=hash_bins, hash_bin_rows=hash_bin_rows,
                   value_is_index=True, value_lo=0.0, value_scale=1.0,
                   value_sort_bits=3, **packed_kw)
    # Headline: the hash-binned sortless stage — what segment_sort="auto"
    # compiles for this COUNT+SUM shape under the exactness gate.
    out["hash_partitions_per_sec"] = out["partitions_per_sec"] = round(
        measure(make_step(**hash_kw),
                [spid, spk, svalue.astype(np.int32)], costs["hash"]), 1)
    # Counter-vs-model honesty check: the bytes credited during the
    # timed runs must equal the statically summed model (3 timed
    # executions per config; the hash config contributes zero).
    modeled = 3 * sum(costs[c]["operand_bytes"] for c in costs)
    measured = profiler.event_count(columnar.EVENT_SORT_BYTES) \
        - bytes_before
    out["modeled_vs_measured_sort_bytes"] = {
        "modeled": modeled, "measured_counter": measured,
        "ratio": round(measured / max(modeled, 1), 4),
    }
    return out


# VECTOR_SUM row (ROADMAP item 5): k=64 dense vectors are 64x the value
# bytes per row, so the row count scales down to keep the resident
# footprint near the scalar headline's; partitions scale with it so
# density (rows per partition) matches the headline shape.
VEC_ROWS = int(os.environ.get("BENCH_VECTOR_ROWS", 2_000_000))
VEC_DIM = 64
VEC_PARTITIONS = max(VEC_ROWS * N_PARTITIONS // N_ROWS, 1)

# PERCENTILE row: the streamed quantile path holds a dense
# [partitions, 16^4 leaves] histogram, so the partition count is bounded
# by the device histogram budget (ops/quantiles.MAX_HISTOGRAM_ELEMENTS),
# not by the scatter passes; rows stay above MIN_STREAM_ROWS so the row
# masks ride the streamed (tiled-sort) kernels.
PCT_ROWS = int(os.environ.get("BENCH_PCT_ROWS", 4_000_000))
PCT_PARTITIONS = int(os.environ.get("BENCH_PCT_PARTITIONS", 2_000))


def _engine_row(make_data, params, n_partitions, n_runs=2):
    """Generic engine e2e row -> (partitions/sec, per-phase dict): the
    same warmup + min-of-n + stage-collection protocol as bench_e2e, for
    metrics beyond COUNT+SUM (VECTOR_SUM, PERCENTILE)."""
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import profiler

    def run(seed):
        with profiler.collect_stage_times() as stages:
            t0 = time.perf_counter()
            accountant = pdp.NaiveBudgetAccountant(EPS, DELTA)
            engine = pdp.JaxDPEngine(accountant, seed=seed)
            result = engine.aggregate(make_data(), params)
            accountant.compute_budgets()
            cols = result.to_columns()
            assert int(np.asarray(cols["keep_mask"]).sum()) > 0
            elapsed = time.perf_counter() - t0
        return elapsed, dict(stages)

    run(100)  # warmup/compile
    results = [run(i) for i in range(n_runs)]
    best_s, best_stages = min(results, key=lambda r: r[0])
    return n_partitions / best_s, _coarse_phases(best_stages, best_s)


def bench_vector_sum(n_runs=2):
    """VECTOR_SUM (k=64) through the full engine path."""
    import pipelinedp_tpu as pdp

    rng = np.random.default_rng(3)
    pk = np.minimum((VEC_PARTITIONS * rng.random(VEC_ROWS)**4).astype(
        np.int32), VEC_PARTITIONS - 1)
    pid = rng.integers(0, max(VEC_ROWS // 10, 1), VEC_ROWS,
                       dtype=np.int32)
    vec = rng.integers(1, 6, (VEC_ROWS, VEC_DIM)).astype(np.float32)
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.VECTOR_SUM],
        max_partitions_contributed=L0_CAP,
        max_contributions_per_partition=LINF_CAP,
        vector_size=VEC_DIM,
        vector_max_norm=5.0,
        vector_norm_kind=pdp.NormKind.Linf)
    return _engine_row(
        lambda: pdp.ColumnarData(pid=pid, pk=pk, value=vec), params,
        VEC_PARTITIONS, n_runs=n_runs)


def bench_percentile(n_runs=2):
    """PERCENTILE(50)+PERCENTILE(90) through the streamed quantile path."""
    import pipelinedp_tpu as pdp

    rng = np.random.default_rng(4)
    pk = np.minimum((PCT_PARTITIONS * rng.random(PCT_ROWS)**4).astype(
        np.int32), PCT_PARTITIONS - 1)
    pid = rng.integers(0, max(PCT_ROWS // 10, 1), PCT_ROWS,
                       dtype=np.int32)
    # Integer grid values: the wire ships affine plane indices, so the
    # streamed row-mask kernel exercises the narrow tiled sort.
    value = rng.integers(0, 101, PCT_ROWS).astype(np.float32)
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.PERCENTILE(50), pdp.Metrics.PERCENTILE(90)],
        max_partitions_contributed=L0_CAP,
        max_contributions_per_partition=LINF_CAP,
        min_value=0.0,
        max_value=100.0)
    return _engine_row(
        lambda: pdp.ColumnarData(pid=pid, pk=pk, value=value), params,
        PCT_PARTITIONS, n_runs=n_runs)


def bench_utility_sweep():
    """BASELINE.md #5: 64-configuration multi-parameter utility-analysis
    sweep (COUNT+SUM+PRIVACY_ID_COUNT error grids) on the device vs the
    host numpy oracle. Returns (device_sec, host_sec)."""
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu.analysis import (cross_partition, data_structures,
                                         per_partition)
    from pipelinedp_tpu.analysis.pre_aggregation import PreAggregates

    n_groups = int(os.environ.get("BENCH_SWEEP_GROUPS", 2_000_000))
    n_parts = int(os.environ.get("BENCH_SWEEP_PARTITIONS", 100_000))
    n_cfg = 64
    rng = np.random.default_rng(1)
    counts = rng.integers(1, 10, n_groups).astype(np.float64)
    pre = PreAggregates(
        pk_ids=rng.integers(0, n_parts, n_groups).astype(np.int32),
        counts=counts,
        sums=counts * rng.uniform(0, 5, n_groups),
        n_partitions=rng.integers(1, 50, n_groups).astype(np.int32),
        pk_vocab=None)
    params = pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM,
                 pdp.Metrics.PRIVACY_ID_COUNT],
        noise_kind=pdp.NoiseKind.GAUSSIAN,
        max_partitions_contributed=8,
        max_contributions_per_partition=4,
        min_sum_per_partition=0.0,
        max_sum_per_partition=5.0)
    multi = data_structures.MultiParameterConfiguration(
        max_partitions_contributed=[1, 2, 3, 4, 6, 8, 12, 16] * 8,
        max_contributions_per_partition=[1, 2, 4, 8] * 16,
        min_sum_per_partition=[0.0] * n_cfg,
        max_sum_per_partition=[float(1 + i % 10) for i in range(n_cfg)])
    options = data_structures.UtilityAnalysisOptions(
        epsilon=4.0, delta=1e-5, aggregate_params=params,
        multi_param_configuration=multi)
    configs = per_partition.resolve_config_budgets(options,
                                                   public_partitions=True)
    metrics = list(params.metrics)

    def run(use_device):
        # Full sweep pipeline: error grids + fused cross-partition report
        # reduction (what parameter_tuning.tune consumes).
        t0 = time.perf_counter()
        arrays = per_partition.compute_per_partition_arrays(
            pre, configs, metrics, public_partitions=True,
            n_partitions=n_parts, use_device=use_device)
        reports = cross_partition.build_reports_with_histogram(
            arrays, metrics, public_partitions=True)
        assert len(reports) == n_cfg
        return time.perf_counter() - t0

    run(True)  # warmup/compile
    device_sec = min(run(True) for _ in range(2))
    host_sec = run(False)
    return device_sec, host_sec


def bench_serving(pid, pk, value):
    """Resident-dataset serving row (ISSUE 9): cold-query vs warm-query
    partitions/sec, queries/sec at batch widths {1, 8, 32, 256} of
    planned configs, resident-cache bytes, and per-query epilogue trace
    counts across a 3-query session.

    Cold = a fresh engine run on raw columns (paying encode + sort +
    transfer), with the session's chunk count so the comparison is
    like-for-like. Warm = the same query answered from the resident
    session: query 1 replays the retained wire (kernel only), queries
    2..3 repeat the same seed/config and ride the bound cache (epilogue
    only). The phase dict of the first warm query is the structural
    evidence that the encode/sort/transfer phase keys are GONE, not just
    small.
    """
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import profiler, serving
    from pipelinedp_tpu.ops import finalize

    params = _params()
    out = {}
    data = pdp.ColumnarData(pid=pid, pk=pk, value=value)
    t0 = time.perf_counter()
    session = serving.DatasetSession(data)
    out["ingest_s"] = round(time.perf_counter() - t0, 3)

    def cold_run(seed):
        with profiler.collect_stage_times() as stages:
            t0 = time.perf_counter()
            acc = pdp.NaiveBudgetAccountant(EPS, DELTA)
            eng = pdp.JaxDPEngine(acc, seed=seed,
                                  stream_chunks=session.n_chunks)
            res = eng.aggregate(
                pdp.ColumnarData(pid=pid, pk=pk, value=value), params)
            acc.compute_budgets()
            assert int(np.asarray(res.to_columns()["keep_mask"]).sum()) > 0
            return time.perf_counter() - t0, dict(stages)

    cold_run(100)  # warmup/compile
    cold_s, cold_stages = min((cold_run(i) for i in range(2)),
                              key=lambda r: r[0])
    out["cold_partitions_per_sec"] = round(N_PARTITIONS / cold_s, 1)
    out["cold_phases"] = _coarse_phases(cold_stages, cold_s)

    # 3-query session, same seed + config: query 1 replays the wire
    # through the kernel, queries 2..3 are bound-cache hits (epilogue +
    # host noise only — the repeat-query serving shape).
    warm_times, traces = [], []
    for q in range(3):
        before = finalize.trace_count()
        with profiler.collect_stage_times() as stages:
            t0 = time.perf_counter()
            cols = session.query(params, epsilon=EPS, delta=DELTA,
                                 seed=0).to_columns()
            assert int(np.asarray(cols["keep_mask"]).sum()) > 0
            warm_times.append(time.perf_counter() - t0)
        traces.append(finalize.trace_count() - before)
        if q == 0:
            out["warm_first_phases"] = _coarse_phases(dict(stages),
                                                      warm_times[0])
            # Amortization evidence: these phase keys must be ABSENT.
            out["warm_encode_sort_phase_keys"] = sorted(
                k for k in stages
                if k.startswith(("dp/encode", "dp/wire_",
                                 "dp/stream_slab_")))
    out["warm_first_query_partitions_per_sec"] = round(
        N_PARTITIONS / warm_times[0], 1)
    out["warm_query_partitions_per_sec"] = round(
        N_PARTITIONS / min(warm_times), 1)
    out["warm_vs_cold"] = round(cold_s / min(warm_times), 2)
    out["per_query_epilogue_traces"] = traces

    # Per-row trace (ISSUE 11): one extra (untimed) warm query exported
    # through session.query(trace_path=) — the published Chrome trace
    # shows the admission -> bound-cache/replay -> finalize span tree of
    # the repeat-query serving shape.
    try:
        from pipelinedp_tpu.obs import trace as obs_trace
        obs_trace.install(obs_trace.Tracer())
        try:
            trace_file = os.path.join(_trace_dir(), "serving_warm.json")
            session.query(params, epsilon=EPS, delta=DELTA, seed=0,
                          trace_path=trace_file).to_columns()
            out["trace_file"] = trace_file
        finally:
            obs_trace.shutdown()
    except Exception as e:  # noqa: BLE001 — tracing never fails the row
        out["trace_error"] = f"{type(e).__name__}: {e}"[:120]
    # This session's released-outcome audit slice (counts only — the
    # row is trajectory data, not the trail itself).
    out["audit_records"] = len(session.audit_trail)

    # Heavy-traffic shape (ISSUE 17): wide batches repeat a small pool
    # of distinct configs, the way production query streams repeat hot
    # queries — the planner dedupes the repeats to one replay lane each
    # and overlaps per-config finalizes with the next group's replay,
    # so queries/sec grows with width instead of shrinking.
    def batch_configs(width, base_seed):
        seeds = [base_seed + i for i in range(min(width, 4))]
        return [
            serving.QueryConfig(
                metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
                epsilon=EPS, delta=DELTA,
                max_partitions_contributed=L0_CAP,
                max_contributions_per_partition=LINF_CAP,
                min_value=0.0, max_value=5.0, seed=seeds[i % len(seeds)])
            for i in range(width)
        ]

    out["batched"] = {}
    for width in (1, 8, 32, 256):
        session.query_batch(batch_configs(width, 10_000 * width))  # compile
        t0 = time.perf_counter()
        session.query_batch(batch_configs(width, 10_000 * width + 500))
        dt = time.perf_counter() - t0
        out["batched"][f"width_{width}_queries_per_sec"] = round(
            width / dt, 2)
    # Config-for-config parity evidence: the batched releases equal the
    # sequential releases bit-for-bit, under seeded device noise (the
    # secure host-noise default draws the process RNG and is
    # unreproducible by design). Sequential runs on a fresh session
    # over the same columns — the at-most-once release journal
    # (correctly) refuses re-releasing a seed within one session.
    parity_cfgs = batch_configs(4, 77_000)
    batch_outs = session.query_batch(parity_cfgs, secure_host_noise=False)
    seq_session = serving.DatasetSession(data, n_chunks=session.n_chunks)
    for cfg, got in zip(parity_cfgs, batch_outs):
        want = seq_session.query(params, epsilon=EPS, delta=DELTA,
                                 seed=cfg.seed,
                                 secure_host_noise=False).to_columns()
        for name in want:
            # NaN-aware: released count/sum hold NaN for dropped
            # partitions, and NaN != NaN under plain array_equal.
            a, b = np.asarray(want[name]), np.asarray(got[name])
            np.testing.assert_array_equal(
                a, b, err_msg=(f"batched release diverged: "
                               f"seed={cfg.seed} col={name}"))
    seq_session.close()
    out["batched"]["parity_configs_bitwise_identical"] = len(parity_cfgs)
    stats = session.stats()
    stats.pop("tenants", None)
    out["resident"] = stats
    out["planner"] = stats["planner"]
    out["serving_counters"] = serving.serving_counters()
    out["fleet"] = _bench_serving_fleet(session, params, cold_s)
    session.close()
    return out


def _bench_serving_fleet(session, params, cold_s):
    """Durable-fleet sub-row (ISSUE 10): save/reopen timings, the
    reopen-vs-cold warm-query ratio (the durability cost in the
    trajectory), and the demotion / rehydration / shedding / deadline
    counters — each machinery deliberately engaged once so a zero in
    the trajectory means a regression, not dead code."""
    import tempfile

    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import runtime, serving

    out = {}
    with tempfile.TemporaryDirectory() as td:
        store = serving.SessionStore(td)
        t0 = time.perf_counter()
        session.save(store)
        out["save_s"] = round(time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        reopened = store.open(session.name)
        out["reopen_s"] = round(time.perf_counter() - t0, 3)
        # Same seed/config as the warm loop: the spilled bound-cache
        # entry re-hydrated, so this is the repeat-query serving shape
        # after a process restart.
        t0 = time.perf_counter()
        cols = reopened.query(params, epsilon=EPS, delta=DELTA,
                              seed=0).to_columns()
        reopen_warm_s = time.perf_counter() - t0
        assert int(np.asarray(cols["keep_mask"]).sum()) > 0
        out["reopen_warm_query_partitions_per_sec"] = round(
            N_PARTITIONS / reopen_warm_s, 1)
        out["reopen_warm_vs_cold"] = round(cold_s / reopen_warm_s, 2)

        # The demotion ladder: a 1-byte fleet budget forces the
        # reopened session down device -> host -> disk when a second
        # session is admitted; querying it re-hydrates on demand.
        manager = serving.SessionManager(store, budget_bytes=1,
                                         max_inflight=1)
        manager.attach(reopened)
        rng = np.random.default_rng(7)
        small = pdp.ColumnarData(
            pid=rng.integers(0, 1000, 50_000).astype(np.int32),
            pk=rng.integers(0, 256, 50_000).astype(np.int32),
            value=rng.uniform(0, 5, 50_000).astype(np.float32))
        manager.create("fleet-b", small, n_chunks=2)
        manager.query(session.name, params, epsilon=EPS, delta=DELTA,
                      seed=1)

        # Overload: the gate is full from this thread, so the query
        # sheds typed (and its cost is the exception, not a queue).
        try:
            with manager.admission():
                manager.query(session.name, params, epsilon=EPS,
                              delta=DELTA, seed=2)
        except serving.SessionOverloadedError:
            pass

        # Deadline: a scripted 5s hang against a 1s deadline trips the
        # typed deadline error within the budget.
        injector = runtime.FaultInjector(
            [runtime.FaultSpec("hang", at_slab=0, hang_s=5.0)])
        try:
            manager.query(session.name, params, epsilon=EPS, delta=DELTA,
                          seed=3, deadline_s=1.0, fault_injector=injector)
        except serving.QueryDeadlineError:
            pass

        out["fleet_counters"] = serving.fleet_counters(manager)
        manager.remove(session.name)
        manager.close()
    return out


# Live-session row (ISSUE 15): streaming-append + continual-release
# shape. Epoch batches are sized so the row finishes in seconds while
# every append still pays the full commit path (micro-encode gate,
# fsync'd WAL record, union re-fold through the pinned chunk schedule).
LIVE_EPOCHS = int(os.environ.get("BENCH_LIVE_EPOCHS", 6))
LIVE_EPOCH_ROWS = int(os.environ.get("BENCH_LIVE_ROWS", 200_000))
LIVE_PARTITIONS = 10_000


def bench_live():
    """Live-session row (ISSUE 15): append rows/sec through the fsync'd
    WAL commit path, scheduled release windows/sec through the tenant
    at-most-once journal, the warm full-union query, and the
    live_counters() delta — so streaming ingest is tracked in the
    trajectory the way batch serving is. Deterministic host noise
    (secure_host_noise=False) keeps the row reproducible."""
    import tempfile

    from pipelinedp_tpu import serving
    from pipelinedp_tpu.serving import live as live_mod

    out = {}
    rng = np.random.default_rng(9)
    epochs = [
        (rng.integers(0, max(LIVE_EPOCH_ROWS // 10, 1), LIVE_EPOCH_ROWS,
                      dtype=np.int32),
         rng.integers(0, LIVE_PARTITIONS, LIVE_EPOCH_ROWS,
                      dtype=np.int32),
         rng.integers(1, 6, LIVE_EPOCH_ROWS).astype(np.float32))
        for _ in range(LIVE_EPOCHS)
    ]
    counters_before = live_mod.live_counters()
    with tempfile.TemporaryDirectory() as td:
        store = serving.SessionStore(td)
        session = serving.LiveDatasetSession.create(
            store=store, name="bench-live",
            public_partitions=list(range(LIVE_PARTITIONS)),
            n_chunks=4, window=serving.WindowSpec(size=2),
            secure_host_noise=False)
        session.register_tenant("bench", 1e6, 1 - 1e-9)
        t0 = time.perf_counter()
        for pid, pk, value in epochs:
            session.append(pid, pk, value)
        append_s = time.perf_counter() - t0
        out["append_rows_per_sec"] = round(
            LIVE_EPOCHS * LIVE_EPOCH_ROWS / append_s, 1)
        out["append_epochs_per_sec"] = round(LIVE_EPOCHS / append_s, 2)
        sched = session.release_schedule(
            "bench-sched", _params(), epsilon=EPS, delta=DELTA,
            tenant="bench", base_seed=17, secure_host_noise=False)
        due = len(sched.due_windows())
        t0 = time.perf_counter()
        records = sched.tick()
        tick_s = time.perf_counter() - t0
        assert len(records) == due and due > 0
        assert all(r["outcome"] == "released" for r in records)
        out["windows_released"] = due
        out["release_windows_per_sec"] = round(due / tick_s, 2)
        # The warm full-union query a live session serves between
        # scheduled releases (the folded union wire is resident).
        t0 = time.perf_counter()
        cols = session.query(_params(), epsilon=EPS, delta=DELTA,
                             seed=5, secure_host_noise=False).to_columns()
        union_s = time.perf_counter() - t0
        assert int(np.asarray(cols["keep_mask"]).sum()) > 0
        out["union_query_partitions_per_sec"] = round(
            LIVE_PARTITIONS / union_s, 1)
        out["status"] = session.live_status()
        sched.close()
        session.close()
    after = live_mod.live_counters()
    out["counters"] = {k: after[k] - counters_before[k] for k in after}
    return out


# Fleet-failover row (ISSUE 19): sized so the row finishes in seconds
# while the follower still replays every epoch digest-verified and the
# promotion pays the real lease-takeover + writable-reopen path.
FLEET_EPOCHS = int(os.environ.get("BENCH_FLEET_EPOCHS", 3))
FLEET_EPOCH_ROWS = int(os.environ.get("BENCH_FLEET_ROWS", 50_000))
FLEET_PARTITIONS = 2_000


def bench_fleet():
    """Fleet-failover row (ISSUE 19): follower replication lag over a
    digest-verified WAL tail, hedged warm-read hit rate through the
    router, and the failover headline — seconds from a dead primary to
    a promoted follower that has taken the lease, reopened writable,
    and committed its first append (``failovers_per_sec`` feeds the
    regress gate as its higher-is-better reciprocal)."""
    import tempfile

    from pipelinedp_tpu import serving
    from pipelinedp_tpu.runtime import watchdog as watchdog_mod
    from pipelinedp_tpu.serving import fleet as fleet_mod

    out = {}
    rng = np.random.default_rng(13)
    epochs = [
        (rng.integers(0, max(FLEET_EPOCH_ROWS // 10, 1),
                      FLEET_EPOCH_ROWS, dtype=np.int32),
         rng.integers(0, FLEET_PARTITIONS, FLEET_EPOCH_ROWS,
                      dtype=np.int32),
         rng.integers(1, 6, FLEET_EPOCH_ROWS).astype(np.float32))
        for _ in range(FLEET_EPOCHS + 1)
    ]
    with tempfile.TemporaryDirectory() as td:
        store = serving.SessionStore(td)
        primary = serving.LiveDatasetSession.create(
            store=store, name="bench-fleet",
            public_partitions=list(range(FLEET_PARTITIONS)),
            n_chunks=4, window=serving.WindowSpec(size=1),
            secure_host_noise=False)
        for pid, pk, value in epochs[:FLEET_EPOCHS]:
            primary.append(pid, pk, value)
        before = fleet_mod.fleet_counters()
        t0 = time.perf_counter()
        follower = fleet_mod.FollowerSession(store, "bench-fleet")
        while follower.replication_lag()["records_behind"] > 0:
            follower.poll()
        out["follower_attach_s"] = round(time.perf_counter() - t0, 4)
        out["replication"] = follower.replication_lag()
        # Hedged warm reads: a burnt deadline routes the tenantless
        # read to the replica instead of betting on the primary.
        router = fleet_mod.FleetRouter()
        router.add_host("primary", primary)
        router.add_follower(follower)
        t0 = time.perf_counter()
        n_reads = 4
        for i in range(n_reads):
            router.query(_params(), shard_key=i,
                         deadline=watchdog_mod.Deadline.after(0.0),
                         epsilon=EPS, delta=DELTA, seed=100 + i,
                         secure_host_noise=False)
        hedge_s = time.perf_counter() - t0
        counters = fleet_mod.fleet_counters()
        hedged = counters["hedged_reads"] - before["hedged_reads"]
        out["hedged_reads"] = hedged
        out["hedged_hit_rate"] = round(
            (counters["hedged_hits"] - before["hedged_hits"])
            / max(hedged, 1), 3)
        out["hedged_reads_per_sec"] = round(n_reads / hedge_s, 2)
        # Failover: the primary goes away; the follower takes the
        # lease (fencing token bump), reopens writable, and proves the
        # new primary with one committed append.
        primary.close()
        t0 = time.perf_counter()
        promoted = follower.promote()
        result = promoted.append(*epochs[FLEET_EPOCHS])
        failover_s = time.perf_counter() - t0
        assert result.committed
        out["failover_time_s"] = round(failover_s, 4)
        out["failovers_per_sec"] = round(1.0 / failover_s, 3)
        out["lease"] = promoted.lease.status()
        final = fleet_mod.fleet_counters()
        out["counters"] = {k: final[k] - before[k] for k in final}
        promoted.close()
    return out


def bench_cpu_baseline() -> float:
    import pipelinedp_tpu as pdp

    rng = np.random.default_rng(0)
    pk = np.minimum((CPU_PARTITIONS * rng.random(CPU_ROWS)**4).astype(int),
                    CPU_PARTITIONS - 1)
    rows = list(
        zip(
            rng.integers(0, max(CPU_ROWS // 10, 1), CPU_ROWS).tolist(),
            pk.tolist(),
            rng.uniform(0, 5, CPU_ROWS).tolist(),
        ))
    params = _params()
    extractors = pdp.DataExtractors(privacy_id_extractor=lambda r: r[0],
                                    partition_extractor=lambda r: r[1],
                                    value_extractor=lambda r: r[2])
    t0 = time.perf_counter()
    accountant = pdp.NaiveBudgetAccountant(EPS, DELTA)
    engine = pdp.DPEngine(accountant, pdp.LocalBackend())
    result = engine.aggregate(rows, params, extractors)
    accountant.compute_budgets()
    n_out = sum(1 for _ in result)
    elapsed = time.perf_counter() - t0
    assert n_out > 0
    return CPU_PARTITIONS / elapsed


def _metrics_snapshot():
    """The obs metrics-registry JSON snapshot (histograms arrive as
    cumulative bucket counts + sum + count, the Prometheus shape)."""
    from pipelinedp_tpu.obs import metrics as obs_metrics

    return obs_metrics.default_registry().snapshot()


def _resilience_counters():
    """Runtime resilience counters (retries, degradations, resumes,
    checkpoint_bytes, native_fallbacks, watchdog_timeouts,
    hangs_detected, journal_recoveries, journal_bytes —
    pipelinedp_tpu/runtime/). All keys always present; a clean run
    reports zeros, and a run that had to retry/degrade/resume — or had a
    hang cut off by the dispatch watchdog, or recovered a durable
    release journal — shows it here instead of hiding it in the timings,
    so the chaos trajectory is tracked like perf."""
    from pipelinedp_tpu import runtime

    return runtime.resilience_counters()


def main():
    from pipelinedp_tpu import compile_cache
    compile_cache.configure(os.path.dirname(os.path.abspath(__file__)))
    cpu_pps = bench_cpu_baseline()
    steady = {}
    try:
        pid, pk, value = _host_columns()
        # Steady-state rows run FIRST (cold process caches) so the
        # first-call column genuinely includes every compile; the headline
        # e2e below then starts warm, as before (warmup + min-of-3).
        steady["e2e_steady"] = bench_e2e_steady(pid, pk, value)
        steady["e2e_device_noise_steady"] = bench_e2e_steady(
            pid, pk, value, n_calls=3, secure_host_noise=False)
    except Exception as e:  # noqa: BLE001
        steady["e2e_steady_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        e2e_pps, e2e_phases = bench_e2e(pid, pk, value)
        kernel = bench_kernel(pid, pk, value)
        kernel_pps = kernel["partitions_per_sec"]
    except Exception as e:  # noqa: BLE001 — report the failure, don't crash
        print(json.dumps({
            "metric": "DP-aggregated partitions/sec (COUNT+SUM, 1M keys)",
            "value": 0.0,
            "unit": "partitions/sec",
            "vs_baseline": 0.0,
            "error": f"{type(e).__name__}: {e}"[:300],
            "resilience": _resilience_counters(),
            **steady,
        }))
        sys.exit(0)
    extra = dict(steady)
    try:
        vec_pps, vec_phases = bench_vector_sum()
        extra["vector_sum_k64_partitions_per_sec"] = round(vec_pps, 1)
        extra["vector_sum_k64_phases"] = vec_phases
    except Exception as e:  # noqa: BLE001
        extra["vector_sum_k64_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        pct_pps, pct_phases = bench_percentile()
        extra["percentile_partitions_per_sec"] = round(pct_pps, 1)
        extra["percentile_phases"] = pct_phases
    except Exception as e:  # noqa: BLE001
        extra["percentile_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        # Round-10 e2e A/B: the same engine path with the group stage
        # forced to the tiled sort vs the sortless hash bins — the e2e
        # twin of the kernel A/B (ROADMAP item 3's measurement ask).
        # The headline e2e row above rides "auto", which resolves to
        # hash for this COUNT+SUM shape under the exactness gate.
        from pipelinedp_tpu import profiler as _prof
        from pipelinedp_tpu.ops import columnar as _columnar
        before = {
            k: _prof.event_count(k)
            for k in (_columnar.EVENT_HASH_PASSES,
                      _columnar.EVENT_HASH_OCCUPANCY,
                      _columnar.EVENT_HASH_DEMOTIONS)
        }
        hash_pps, _ = bench_e2e(pid, pk, value, n_runs=2,
                                segment_sort="hash")
        counters = {
            k.rsplit("/", 1)[1]: _prof.event_count(k) - before[k]
            for k in before
        }
        tiled_pps, _ = bench_e2e(pid, pk, value, n_runs=2,
                                 segment_sort=True)
        extra["e2e_segment_sort_ab"] = {
            "hash_partitions_per_sec": round(hash_pps, 1),
            "tiled_partitions_per_sec": round(tiled_pps, 1),
            "hash_vs_tiled": round(hash_pps / max(tiled_pps, 1e-9), 3),
            "hash_counters": counters,
        }
    except Exception as e:  # noqa: BLE001
        extra["e2e_segment_sort_ab_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        # De-confounding row (round-5 advisor): the same shape with
        # uniform CONTINUOUS values, which defeat the affine-integer plane
        # encoding and ship raw float32 — so codec gains (compressible
        # star ratings, headline row) and workload compressibility are
        # reported separately across rounds.
        rng = np.random.default_rng(7)
        uvalue = rng.uniform(0.0, 5.0, N_ROWS).astype(np.float32)
        uniform_pps, uniform_phases = bench_e2e(pid, pk, uvalue, n_runs=2)
        extra["e2e_uniform_float_partitions_per_sec"] = round(uniform_pps, 1)
        extra["e2e_uniform_float_vs_baseline"] = round(
            uniform_pps / cpu_pps, 2)
        extra["e2e_uniform_float_phases"] = uniform_phases
        del uvalue
    except Exception as e:  # noqa: BLE001
        extra["e2e_uniform_float_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        # Serving row (ISSUE 9): warm queries must drop the
        # encode/sort/transfer phase keys entirely and amortize to >=5x
        # the cold-query throughput; the trajectory JSON tracks it like
        # COUNT+SUM.
        extra["serving"] = bench_serving(pid, pk, value)
    except Exception as e:  # noqa: BLE001
        extra["serving_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        # Live-session row (ISSUE 15): streaming append throughput and
        # scheduled windowed releases, tracked like batch serving.
        extra["live"] = bench_live()
    except Exception as e:  # noqa: BLE001
        extra["live_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        # Fleet-failover row (ISSUE 19): follower replication, hedged
        # warm reads, and the promote-to-first-commit failover time.
        extra["fleet"] = bench_fleet()
    except Exception as e:  # noqa: BLE001
        extra["fleet_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        sweep_dev_sec, sweep_host_sec = bench_utility_sweep()
        extra.update({
            # BASELINE.md #5: 64-config multi-parameter sweep, 2M groups.
            "utility_sweep_64cfg_sec": round(sweep_dev_sec, 3),
            "utility_sweep_host_sec": round(sweep_host_sec, 3),
            "utility_sweep_vs_host": round(sweep_host_sec / sweep_dev_sec,
                                           2),
        })
    except Exception as e:  # noqa: BLE001
        extra["utility_sweep_error"] = f"{type(e).__name__}: {e}"[:200]
    from pipelinedp_tpu.native import loader
    from pipelinedp_tpu.ops import streaming as streaming_mod

    print(json.dumps({
        "metric": "DP-aggregated partitions/sec (COUNT+SUM, 1M keys), "
                  "end-to-end through JaxDPEngine.aggregate",
        # The workload-shape signature the bench regression gate
        # (obs/regress.py) groups comparable rounds by — the resolved
        # BENCH_* knobs, explicit so the gate no longer has to parse
        # them out of the recorded command line.
        "shape": {
            "BENCH_ROWS": str(N_ROWS),
            "BENCH_PARTITIONS": str(N_PARTITIONS),
            "BENCH_CPU_ROWS": str(CPU_ROWS),
            "BENCH_VECTOR_ROWS": str(VEC_ROWS),
            "BENCH_PCT_ROWS": str(PCT_ROWS),
            "BENCH_PCT_PARTITIONS": str(PCT_PARTITIONS),
            "BENCH_LIVE_EPOCHS": str(LIVE_EPOCHS),
            "BENCH_LIVE_ROWS": str(LIVE_EPOCH_ROWS),
            "BENCH_SWEEP_GROUPS": str(
                os.environ.get("BENCH_SWEEP_GROUPS", 2_000_000)),
            "BENCH_SWEEP_PARTITIONS": str(
                os.environ.get("BENCH_SWEEP_PARTITIONS", 100_000)),
        },
        "value": round(e2e_pps, 1),
        "unit": "partitions/sec",
        "vs_baseline": round(e2e_pps / cpu_pps, 2),
        "kernel_partitions_per_sec": round(kernel_pps, 1),
        "kernel_vs_baseline": round(kernel_pps / cpu_pps, 2),
        # Round-10 tentpole A/B on the kernel-resident row: general (the
        # historical ~305k floor), packed (rounds 6-8 wire kernel), tiled
        # (round-9 segment-local sort), hash (round-10 sortless group
        # stage, the new auto default under the exactness gate) — with
        # the modeled ops/sort_* counters per configuration.
        "kernel_sort": kernel,
        "cpu_baseline_partitions_per_sec": round(cpu_pps, 1),
        "e2e_phases": e2e_phases,
        # Encode/pipeline tuning in effect (README "Tuning knobs"):
        # encode_threads 0 = auto (hardware concurrency, capped 16).
        "encode_threads": loader.encode_threads(),
        "host_cores": os.cpu_count(),
        "prefetch_depth": streaming_mod.prefetch_depth(),
        "resilience": _resilience_counters(),
        # The full typed-metrics registry snapshot (ISSUE 11): every
        # counter/gauge/histogram the run populated, plus the legacy
        # event namespace — the same storage `to_prometheus()` scrapes.
        "metrics": _metrics_snapshot(),
        **extra,
    }))


if __name__ == "__main__":
    main()
