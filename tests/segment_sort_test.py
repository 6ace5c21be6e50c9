"""Round-9 bucketed segment-local sort: bit-parity matrix + knob tests.

The contract pinned here (ISSUE 7 tentpole): ``segment_sort`` is pure
kernel geometry. Released accumulators, kept partitions, and replayed
sampling are BIT-identical whether the packed 3-key bounding sort runs
globally over the whole chunk (legacy, ``segment_sort=False``) or over
fixed-width bucket tiles (``segment_sort=True``), with the narrow value
payload and int32 group accumulation that ``"auto"``'s packed sort also
carries, across:

  {RLE, PID_PLANES} x {group-clip, no-clip} x {single-device, mesh8}
  x {compact merge on/off},

plus resume-from-checkpoint parity with tiling enabled, the
``presorted_fits`` bit-capacity boundary, the int-accumulation exactness
gate, and the VECTOR_SUM packed-sort plumbing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import profiler
from pipelinedp_tpu import runtime
from pipelinedp_tpu.ops import columnar, streaming, wirecodec
from pipelinedp_tpu.parallel import sharded


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return sharded.make_mesh(8)


@pytest.fixture(autouse=True)
def _reset_sampler_counters():
    profiler.reset_events("ops/sampler_chunks/")
    yield


def _sampler_chunks() -> dict:
    """Executed chunk launches per sampler kind since the last reset
    (kinds that ran none are left out)."""
    counts = {kind: profiler.event_count(columnar.sampler_event(kind))
              for kind in ("hash", "tiled", "packed", "general")}
    return {kind: n for kind, n in counts.items() if n}


def _rle_data(n=60_000, n_parts=300, seed=0, integer_values=True):
    """Repetitive pids (~20 rows/user) -> PID_RLE wire, small max_run ->
    tiles engage; integer values -> VALUE_PLANES -> narrow sort payload +
    int32 accumulation ride along."""
    rng = np.random.default_rng(seed)
    pid = rng.integers(0, n // 20, n).astype(np.int64)
    pk = rng.integers(0, n_parts, n).astype(np.int32)
    if integer_values:
        value = rng.integers(0, 6, n).astype(np.float32)
    else:
        value = rng.uniform(0, 5, n).astype(np.float32)
    return pid, pk, value


def _planes_data(n=60_000, n_parts=300, seed=1):
    """Near-unique pids -> PID_PLANES wire (arrival order, no host sort;
    tiling cannot apply — parity must hold trivially)."""
    rng = np.random.default_rng(seed)
    pid = rng.permutation(n).astype(np.int64)
    pk = rng.integers(0, n_parts, n).astype(np.int32)
    value = rng.integers(0, 6, n).astype(np.float32)
    return pid, pk, value


def _stream(pid, pk, value, *, mesh=None, n_parts=300, has_group_clip=True,
            n_chunks=8, **kw):
    clips = (dict(row_clip_lo=-np.inf, row_clip_hi=np.inf, middle=0.0,
                  group_clip_lo=-30.0, group_clip_hi=30.0)
             if has_group_clip else
             dict(row_clip_lo=0.0, row_clip_hi=5.0, middle=2.5,
                  group_clip_lo=-np.inf, group_clip_hi=np.inf))
    args = (jax.random.PRNGKey(7), pid, pk, value)
    common = dict(num_partitions=n_parts, linf_cap=6, l0_cap=8,
                  has_group_clip=has_group_clip, n_chunks=n_chunks, **clips,
                  **kw)
    if mesh is not None:
        accs = sharded.stream_bound_and_aggregate(mesh, *args, **common)
    else:
        accs = streaming.stream_bound_and_aggregate(*args, **common)
    return jax.device_get(accs)


def _assert_bitwise(a, b):
    for name, x, y in zip(a._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)


class TestTiledSortParityMatrix:
    """segment_sort=True vs False, bitwise, across the full matrix."""

    @pytest.mark.parametrize("has_group_clip", [True, False])
    @pytest.mark.parametrize("compact", [True, False])
    def test_rle_single_device(self, has_group_clip, compact):
        pid, pk, value = _rle_data()
        legacy = _stream(pid, pk, value, has_group_clip=has_group_clip,
                         compact_merge=compact, segment_sort=False)
        profiler.reset_events("ops/sampler_chunks/")
        tiled = _stream(pid, pk, value, has_group_clip=has_group_clip,
                        compact_merge=compact, segment_sort=True)
        # Non-vacuous: every chunk ran the tiled sampler.
        assert _sampler_chunks() == {"tiled": 8}
        _assert_bitwise(legacy, tiled)

    @pytest.mark.parametrize("has_group_clip", [True, False])
    @pytest.mark.parametrize("compact", [True, False])
    def test_rle_mesh8(self, mesh, has_group_clip, compact):
        # Two chunks of 8 buckets: each bucket (~2,500 rows) is wider
        # than one tile, so the tiled sampler can engage on the mesh.
        pid, pk, value = _rle_data(n=40_000)
        legacy = _stream(pid, pk, value, mesh=mesh,
                         has_group_clip=has_group_clip,
                         compact_merge=compact, segment_sort=False,
                         n_chunks=2)
        profiler.reset_events("ops/sampler_chunks/")
        tiled = _stream(pid, pk, value, mesh=mesh,
                        has_group_clip=has_group_clip,
                        compact_merge=compact, segment_sort=True,
                        n_chunks=2)
        assert _sampler_chunks() == {"tiled": 2}
        _assert_bitwise(legacy, tiled)

    @pytest.mark.parametrize("has_group_clip", [True, False])
    def test_planes_single_device(self, has_group_clip):
        pid, pk, value = _planes_data()
        legacy = _stream(pid, pk, value, has_group_clip=has_group_clip,
                         segment_sort=False)
        profiler.reset_events("ops/sampler_chunks/")
        tiled = _stream(pid, pk, value, has_group_clip=has_group_clip,
                        segment_sort=True)
        # PID_PLANES rows arrive unsorted: tiling cannot engage — every
        # executed chunk (n_chunks=8) runs the general sort.
        assert _sampler_chunks() == {"general": 8}
        _assert_bitwise(legacy, tiled)

    def test_planes_mesh8(self, mesh):
        pid, pk, value = _planes_data(n=40_000)
        legacy = _stream(pid, pk, value, mesh=mesh, segment_sort=False)
        tiled = _stream(pid, pk, value, mesh=mesh, segment_sort=True)
        _assert_bitwise(legacy, tiled)

    def test_continuous_values_single_device(self):
        # Continuous values defeat the VALUE_PLANES integer grid: the
        # value rides the sort as raw float32 and accumulation stays
        # float — tiling alone must still be bitwise.
        pid, pk, value = _rle_data(integer_values=False)
        legacy = _stream(pid, pk, value, segment_sort=False)
        tiled = _stream(pid, pk, value, segment_sort=True)
        _assert_bitwise(legacy, tiled)

    def test_auto_matches_forced_when_engaged(self):
        # At a shape where forced tiling engages, "auto" still runs the
        # packed global sort on every chunk, and releases the same bits
        # as forced tiling and the round-8 oracle.
        pid, pk, value = _rle_data(n=300_000, seed=3)
        auto = _stream(pid, pk, value, segment_sort="auto")
        assert _sampler_chunks() == {"packed": 8}
        profiler.reset_events("ops/sampler_chunks/")
        forced = _stream(pid, pk, value, segment_sort=True)
        assert _sampler_chunks() == {"tiled": 8}
        legacy = _stream(pid, pk, value, segment_sort=False)
        _assert_bitwise(auto, forced)
        _assert_bitwise(auto, legacy)


class TestTiledResumeParity:
    """Resume-from-checkpoint with tiling enabled stays bitwise."""

    def _stream_tiled(self, pid, pk, value, **kw):
        return _stream(pid, pk, value, segment_sort=True, **kw)

    def test_resume_from_mid_checkpoint_matches(self):
        pid, pk, value = _rle_data()
        full = self._stream_tiled(pid, pk, value)
        store = runtime.InMemoryCheckpointStore()
        policy = runtime.CheckpointPolicy(store=store, run_id="tiled",
                                          delete_on_success=False)
        self._stream_tiled(
            pid, pk, value,
            resilience=runtime.StreamResilience(checkpoint_policy=policy))
        checkpoint = store.load("tiled")
        assert 0 < checkpoint.next_chunk < checkpoint.n_chunks
        resumed = self._stream_tiled(pid, pk, value,
                                     resume_from=checkpoint)
        _assert_bitwise(full, resumed)

    def test_crash_resume_through_engine(self):
        pid, pk, value = _rle_data()
        n_parts = 300

        def run(**engine_kw):
            accountant = pdp.NaiveBudgetAccountant(1e9, 1 - 1e-9)
            engine = pdp.JaxDPEngine(accountant, seed=3, stream_chunks=8,
                                     secure_host_noise=False,
                                     segment_sort=True, **engine_kw)
            params = pdp.AggregateParams(
                metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
                max_partitions_contributed=8,
                max_contributions_per_partition=6,
                min_value=0.0, max_value=5.0)
            result = engine.aggregate(
                pdp.ColumnarData(pid=pid, pk=pk, value=value), params,
                public_partitions=list(range(n_parts)))
            accountant.compute_budgets()
            return result.to_columns()

        clean = run()
        store = runtime.InMemoryCheckpointStore()
        policy = runtime.CheckpointPolicy(store=store, run_id="tiledkill")
        with pytest.raises(runtime.HostCrash):
            run(checkpoint_policy=policy,
                fault_injector=runtime.FaultInjector(
                    [runtime.FaultSpec("host_crash", at_slab=1)]))
        assert store.load("tiledkill").next_chunk > 0
        resumed = run(checkpoint_policy=policy)
        for name in clean:
            np.testing.assert_array_equal(clean[name], resumed[name],
                                          err_msg=name)


class TestSortByteCounters:
    """At the 2^17-partition shape the tiled sampler engages on every
    chunk and releases the same bits as the global sort."""

    def test_tiled_engages_at_128k_partitions(self):
        n_parts = 1 << 17
        rng = np.random.default_rng(5)
        n = 120_000
        pid = rng.integers(0, n // 20, n).astype(np.int64)
        pk = rng.integers(0, n_parts, n).astype(np.int32)
        value = rng.integers(0, 6, n).astype(np.float32)
        legacy = _stream(pid, pk, value, n_parts=n_parts,
                         segment_sort=False)
        assert "tiled" not in _sampler_chunks()
        profiler.reset_events("ops/sampler_chunks/")
        tiled = _stream(pid, pk, value, n_parts=n_parts,
                        segment_sort=True)
        assert _sampler_chunks() == {"tiled": 8}
        _assert_bitwise(legacy, tiled)


class TestPresortedFitsBoundary:
    """packed_key_layout is the single source of truth for the 3-key bit
    budget; the fit flips exactly where the rand field hits its floor."""

    def test_exact_capacity_edge(self):
        # segbits(2^31) = 32, pkbits(2^20) = 20 -> rand = 96-32-32-20 = 12
        # = _MIN_RAND_BITS: the last fitting layout.
        n = 1 << 20
        assert columnar.presorted_fits(n, 1 << 20, max_segments=2**31)
        segbits, pkbits, randbits, padbits = columnar.packed_key_layout(
            n, 1 << 20, max_segments=2**31)
        assert (segbits, pkbits, randbits, padbits) == (32, 20, 12, 0)
        # One more segment bit starves the rand field below the floor.
        assert not columnar.presorted_fits(n, 1 << 20, max_segments=2**32)
        # One more pk bit does the same at fixed segments.
        assert not columnar.presorted_fits(n, 1 << 21, max_segments=2**31)
        assert columnar.presorted_fits(n, 1 << 19, max_segments=2**31)

    def test_fit_iff_rand_floor_over_sweep(self):
        for seg_pow in (1, 8, 16, 24, 31, 32, 40):
            for pk_pow in (1, 10, 20, 30):
                n = 1 << 16
                fits = columnar.presorted_fits(n, 1 << pk_pow,
                                               max_segments=2**seg_pow)
                _, _, randbits, _ = columnar.packed_key_layout(
                    n, 1 << pk_pow, max_segments=2**seg_pow)
                assert fits == (randbits >= columnar._MIN_RAND_BITS)

    def test_layout_always_spans_96_bits_when_fitting(self):
        segbits, pkbits, randbits, padbits = columnar.packed_key_layout(
            1 << 16, 1000, max_segments=4096)
        assert segbits + 32 + pkbits + randbits + padbits == \
            columnar._KEY_BITS


class TestPlanSegmentTiling:
    def _fmt(self, cap=1 << 15, pid_mode=wirecodec.PID_RLE):
        return wirecodec.WireFormat(
            bytes_pid=3, bits_pk=10, cap=cap, ucap=1 << 12,
            value=wirecodec.ValuePlan(wirecodec.VALUE_PLANES, 0.0, 1.0, 3),
            pid_mode=pid_mode)

    def test_auto_requires_enough_tiles(self):
        fmt = self._fmt(cap=1 << 12)
        # auto never tiles; True forces a tile as long as one tile plus
        # its slack leaves the bucket room.
        assert wirecodec.plan_segment_tiling(fmt, "auto", 16).tile_rows == 0
        forced = wirecodec.plan_segment_tiling(fmt, True, 16)
        assert forced.tile_rows == 1024 and forced.tile_slack == 16

    def test_disabled_cases(self):
        fmt = self._fmt()
        assert wirecodec.plan_segment_tiling(fmt, False, 16).tile_rows == 0
        assert wirecodec.plan_segment_tiling(fmt, "auto", -1).tile_rows == 0
        assert wirecodec.plan_segment_tiling(fmt, "auto", 0).tile_rows == 0
        planes = self._fmt(pid_mode=wirecodec.PID_PLANES)
        assert wirecodec.plan_segment_tiling(planes, True, 16).tile_rows \
            == 0
        # A run so long one tile (+slack) would cover the whole bucket.
        assert wirecodec.plan_segment_tiling(
            self._fmt(cap=1 << 12), True, 1 << 11).tile_rows == 0

    def test_slack_bounds_max_run(self):
        fmt = wirecodec.plan_segment_tiling(self._fmt(), True, 100)
        assert fmt.tile_rows >= 4 * 100
        assert fmt.tile_slack >= 100
        assert fmt.tile_rows % 2 == 0 and fmt.tile_slack % 8 == 0

    # (cap, max_run): small buckets, wide ones, and the Netflix Prize's
    # chunk (12,728,480 rows per bucket, a heaviest user of 17,653 rows).
    @pytest.mark.parametrize("cap,max_run", [
        (1 << 15, 16), (1 << 15, 100), (1 << 20, 1000),
        (12_728_480, 17_653), (1 << 24, 100)])
    def test_auto_never_tiles(self, cap, max_run):
        fmt = self._fmt(cap=cap)
        auto = wirecodec.plan_segment_tiling(fmt, "auto", max_run)
        assert (auto.tile_rows, auto.tile_slack) == (0, 0)
        forced = wirecodec.plan_segment_tiling(fmt, True, max_run)
        assert forced.tile_rows >= 4 * max_run
        assert forced.tile_slack >= max_run


class TestIntAccumulationPlan:
    def test_integer_grid_accepted(self):
        plan = columnar.int_accumulation_plan(0.0, 1.0, 3, 0.0, 5.0, 6)
        assert plan == (0, 5)

    def test_infinite_clips_accepted(self):
        plan = columnar.int_accumulation_plan(0.0, 1.0, 3, -np.inf, np.inf,
                                              6)
        assert plan is not None

    def test_rejections(self):
        # Non-integer scale / lo.
        assert columnar.int_accumulation_plan(0.0, 0.5, 3, 0, 5, 6) is None
        assert columnar.int_accumulation_plan(0.25, 1.0, 3, 0, 5, 6) is None
        # Non-integer finite clip bound.
        assert columnar.int_accumulation_plan(0.0, 1.0, 3, 0.0, 4.5,
                                              6) is None
        # NaN clip bound.
        assert columnar.int_accumulation_plan(0.0, 1.0, 3, 0.0, np.nan,
                                              6) is None
        # Magnitude overflow: linf * max|value| >= 2^24.
        assert columnar.int_accumulation_plan(0.0, 1.0, 20, 0.0, np.inf,
                                              100) is None
        # Reconstruction overflow: |lo| + max_idx*|scale| >= 2^24
        # (4095 * 4096 = 16_773_120 still fits; 8191 * 4096 does not).
        assert columnar.int_accumulation_plan(0.0, 1 << 12, 12, 0.0,
                                              np.inf, 1) is not None
        assert columnar.int_accumulation_plan(0.0, 1 << 12, 13, 0.0,
                                              np.inf, 1) is None
        # Zero / negative caps.
        assert columnar.int_accumulation_plan(0.0, 1.0, 3, 0.0, 5.0,
                                              0) is None

    def test_non_concrete_cap_rejected(self):
        # A traced cap cannot be bounded statically -> no int plan.
        def probe(cap):
            return columnar.int_accumulation_plan(0.0, 1.0, 3, 0.0, 5.0,
                                                  cap) is None

        assert jax.jit(lambda c: jax.numpy.int32(probe(c)))(6) == 1


class TestTiledKernelUnit:
    """Direct columnar-level parity of the tiled sampler (no wire)."""

    def _sorted_rows(self, n=8_192, n_parts=64, seed=2, runs=12):
        rng = np.random.default_rng(seed)
        pid = np.sort(rng.integers(0, n // runs, n)).astype(np.int32)
        pk = rng.integers(0, n_parts, n).astype(np.int32)
        value = rng.integers(0, 6, n).astype(np.float32)
        valid = np.arange(n) < (n - 100)  # padded tail
        # pid-sorted over the valid prefix (padding rows may be anything).
        return pid, pk, value, valid

    def _kernel(self, pid, pk, value, valid, n_parts, **kw):
        return jax.device_get(columnar.bound_and_aggregate(
            jax.random.PRNGKey(11), pid, pk, value, valid,
            num_partitions=n_parts, linf_cap=3, l0_cap=4,
            row_clip_lo=0.0, row_clip_hi=5.0, middle=2.5,
            group_clip_lo=-np.inf, group_clip_hi=np.inf,
            pid_sorted=True, max_segments=1 << 11, **kw))

    def test_tiled_bitwise_equals_global(self):
        pid, pk, value, valid = self._sorted_rows()
        max_run = int(np.bincount(pid).max())
        base = self._kernel(pid, pk, value, valid, 64)
        tiled = self._kernel(pid, pk, value, valid, 64,
                             tile_rows=1024, tile_slack=max_run)
        _assert_bitwise(base, tiled)

    def test_tiled_narrow_index_int_accumulate(self):
        pid, pk, value, valid = self._sorted_rows()
        max_run = int(np.bincount(pid).max())
        base = self._kernel(pid, pk, value, valid, 64)
        plan = columnar.int_accumulation_plan(0.0, 1.0, 3, 0.0, 5.0, 3)
        assert plan is not None
        narrow = self._kernel(
            pid, pk, value.astype(np.int32), valid, 64,
            tile_rows=1024, tile_slack=max_run, value_is_index=True,
            value_lo=0.0, value_scale=1.0, value_sort_bits=3,
            int_accumulate=True, int_clip_lo=plan[0], int_clip_hi=plan[1])
        _assert_bitwise(base, narrow)

    def test_row_mask_replays_tiled(self):
        pid, pk, value, valid = self._sorted_rows()
        max_run = int(np.bincount(pid).max())
        key = jax.random.PRNGKey(11)
        base = columnar.bound_row_mask(
            key, pid, pk, valid, 3, 4, pid_sorted=True,
            max_segments=1 << 11, num_partitions=64)
        tiled = columnar.bound_row_mask(
            key, pid, pk, valid, 3, 4, pid_sorted=True,
            max_segments=1 << 11, num_partitions=64,
            tile_rows=1024, tile_slack=max_run)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(tiled))

    def test_slack_violation_empties_not_corrupts(self):
        # A pid run longer than tile_slack breaks the binning contract;
        # the kernel's backstop must yield EMPTY accumulators, never a
        # silently re-sampled release.
        n = 4_096
        pid = np.zeros(n, dtype=np.int32)  # one run spanning every tile
        pk = np.zeros(n, dtype=np.int32)
        value = np.ones(n, dtype=np.float32)
        valid = np.ones(n, dtype=bool)
        out = self._kernel(pid, pk, value, valid, 64,
                           tile_rows=1024, tile_slack=8)
        assert float(np.asarray(out.count).sum()) == 0.0


class TestVectorPackedSort:
    """VECTOR_SUM satellite: the packed 3-key sort on pid-sorted rows."""

    def _cols(self, n=20_000, n_parts=50, d=4, seed=6):
        rng = np.random.default_rng(seed)
        pid = np.sort(rng.integers(0, n // 10, n)).astype(np.int32)
        pk = rng.integers(0, n_parts, n).astype(np.int32)
        vec = rng.uniform(-1, 1, (n, d)).astype(np.float32)
        return pid, pk, vec

    def test_presorted_equals_general_when_caps_do_not_bind(self):
        # With caps that never bind, every row is kept under EITHER
        # sampler, so the packed-sort path must produce the exact same
        # sums (the draws differ; the kept set does not).
        pid, pk, vec = self._cols()
        valid = np.ones(len(pid), dtype=bool)
        kw = dict(num_partitions=50, linf_cap=10_000, l0_cap=10_000,
                  max_norm=100.0, norm_ord=0)
        general = columnar.bound_and_aggregate_vector(
            jax.random.PRNGKey(2), pid, pk, vec, valid, **kw)
        packed = columnar.bound_and_aggregate_vector(
            jax.random.PRNGKey(2), pid, pk, vec, valid, pid_sorted=True,
            max_segments=1 << 12, **kw)
        np.testing.assert_array_equal(np.asarray(general[0]),
                                      np.asarray(packed[0]))
        _assert_bitwise(general[1], packed[1])

    def test_packed_caps_enforced(self):
        # Binding caps: the packed sampler must enforce the same bounds
        # (distribution-level, not bitwise, vs the general sampler).
        pid, pk, vec = self._cols()
        valid = np.ones(len(pid), dtype=bool)
        vec = np.abs(vec)
        out, accs = columnar.bound_and_aggregate_vector(
            jax.random.PRNGKey(2), pid, pk, vec, valid,
            num_partitions=50, linf_cap=2, l0_cap=3, max_norm=1.0,
            norm_ord=0, pid_sorted=True, max_segments=1 << 12)
        n_users = len(np.unique(pid))
        # Each user contributes <= l0*linf rows of Linf norm <= 1.
        assert float(np.asarray(out).sum()) <= n_users * 2 * 3 * 4 + 1e-3

    def test_engine_vector_segment_sort_knob(self):
        # segment_sort=False reproduces the legacy unsorted kernel
        # draw-for-draw (deterministic across runs); "auto" host-sorts
        # the rows, so with non-binding caps it keeps the same row set
        # and agrees to float32 association (different segment-sum
        # order, not different sampling).
        rng = np.random.default_rng(8)
        n = 5_000
        data_pid = rng.integers(0, 500, n)
        data_pk = rng.integers(0, 20, n).astype(np.int32)
        vec = rng.uniform(-1, 1, (n, 3)).astype(np.float32)

        def run(segment_sort):
            accountant = pdp.NaiveBudgetAccountant(1e9, 1 - 1e-9)
            engine = pdp.JaxDPEngine(accountant, seed=5,
                                     secure_host_noise=False,
                                     segment_sort=segment_sort)
            params = pdp.AggregateParams(
                metrics=[pdp.Metrics.VECTOR_SUM],
                max_partitions_contributed=1000,
                max_contributions_per_partition=1000,
                vector_size=3, vector_max_norm=100.0,
                vector_norm_kind=pdp.NormKind.Linf)
            result = engine.aggregate(
                pdp.ColumnarData(pid=data_pid, pk=data_pk, value=vec),
                params, public_partitions=list(range(20)))
            accountant.compute_budgets()
            return result.to_columns()

        legacy = run(False)
        np.testing.assert_array_equal(legacy["vector_sum"],
                                      run(False)["vector_sum"])
        auto = run("auto")
        np.testing.assert_allclose(legacy["vector_sum"],
                                   auto["vector_sum"], rtol=1e-4,
                                   atol=1e-4)

    def test_mesh_vector_pid_sorted_exact(self, mesh):
        pid, pk, vec = self._cols(n=16_000)
        valid = np.ones(len(pid), dtype=bool)
        out, _ = sharded.bound_and_aggregate_vector(
            mesh, jax.random.PRNGKey(2), pid, pk, vec, valid,
            num_partitions=50, linf_cap=10_000, l0_cap=10_000,
            max_norm=100.0, norm_ord=0, pid_sorted=True,
            max_segments=1 << 12)
        truth = np.zeros((64, vec.shape[1]), dtype=np.float64)
        np.add.at(truth, pk, vec.astype(np.float64))
        np.testing.assert_allclose(np.asarray(out)[:50], truth[:50],
                                   rtol=1e-4, atol=1e-4)


class TestQuantileTiledReplay:
    """PERCENTILE rides the streamed kernels: the row mask must replay
    the SAME (tiled) sampling as the aggregation kernel, so the released
    quantiles are bitwise invariant to the segment_sort knob."""

    def _run(self, segment_sort):
        rng = np.random.default_rng(9)
        n = 60_000
        pid = rng.integers(0, n // 20, n)
        pk = rng.integers(0, 40, n).astype(np.int32)
        value = rng.integers(0, 101, n).astype(np.float32)
        accountant = pdp.NaiveBudgetAccountant(1e9, 1 - 1e-9)
        engine = pdp.JaxDPEngine(accountant, seed=4, stream_chunks=8,
                                 secure_host_noise=False,
                                 segment_sort=segment_sort)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.PERCENTILE(50),
                     pdp.Metrics.PERCENTILE(90)],
            max_partitions_contributed=8,
            max_contributions_per_partition=6,
            min_value=0.0, max_value=100.0)
        result = engine.aggregate(
            pdp.ColumnarData(pid=pid, pk=pk, value=value), params,
            public_partitions=list(range(40)))
        accountant.compute_budgets()
        return result.to_columns()

    def test_percentiles_bitwise_invariant(self):
        legacy = self._run(False)
        tiled = self._run(True)
        for name in legacy:
            np.testing.assert_array_equal(legacy[name], tiled[name],
                                          err_msg=name)


# (n, statics, kind): one case per branch of columnar.sampler_kind, at a
# row capacity small enough to trace every sampler.
_PLAIN = dict(num_partitions=1 << 10, max_segments=None, pid_sorted=False,
              l1_mode=False, tile_rows=0, tile_slack=0, hash_bins=0,
              hash_bin_rows=0)
_SORTED = dict(_PLAIN, pid_sorted=True, max_segments=32)
_SAMPLER_CASES = {
    "empty": (0, _SORTED, "empty"),
    "general_unsorted": (64, _PLAIN, "general"),
    "general_l1": (64, dict(_SORTED, l1_mode=True, tile_rows=16,
                            tile_slack=8), "general"),
    "general_key_does_not_fit": (
        64, dict(_SORTED, num_partitions=1 << 30, max_segments=1 << 30),
        "general"),
    "packed": (64, _SORTED, "packed"),
    "packed_tile_too_wide": (64, dict(_SORTED, tile_rows=48,
                                      tile_slack=16), "packed"),
    "tiled": (64, dict(_SORTED, tile_rows=16, tile_slack=8), "tiled"),
    "hash": (64, dict(_SORTED, hash_bins=32, hash_bin_rows=8), "hash"),
    "hash_over_tiles": (64, dict(_SORTED, tile_rows=16, tile_slack=8,
                                 hash_bins=32, hash_bin_rows=8), "hash"),
}
_SAMPLERS = {
    "hash": "_bin_rows_and_groups_hash",
    "tiled": "_sample_rows_and_groups_tiled",
    "packed": "_sample_rows_and_groups_presorted",
    "general": "_sample_rows_and_groups",
}


class TestSamplerKind:
    """columnar.sampler_kind is the one sampler decision: its table, and
    _dispatch_sampler tracing exactly the sampler it names."""

    @pytest.mark.parametrize("case", sorted(_SAMPLER_CASES))
    def test_sampler_kind(self, case):
        n, statics, kind = _SAMPLER_CASES[case]
        assert columnar.sampler_kind(n, **statics) == kind

    @pytest.mark.parametrize(
        "case", sorted(c for c in _SAMPLER_CASES if c != "empty"))
    def test_dispatch_follows_sampler_kind(self, case, monkeypatch):
        n, statics, kind = _SAMPLER_CASES[case]
        ran = []
        for name, attr in _SAMPLERS.items():
            def spy(*args, _name=name, _real=getattr(columnar, attr),
                    **kwargs):
                ran.append(_name)
                return _real(*args, **kwargs)
            monkeypatch.setattr(columnar, attr, spy)
        pid = jnp.repeat(jnp.arange(n // 4, dtype=jnp.int32), 4)
        pk = jnp.arange(n, dtype=jnp.int32) % 7
        valid = jnp.ones((n,), dtype=bool)
        l1_cap = jnp.int32(4) if statics["l1_mode"] else None
        kw = {k: v for k, v in statics.items() if k != "l1_mode"}

        def trace(key, value):
            return columnar._dispatch_sampler(
                key, pid, pk, valid, jnp.int32(2), jnp.int32(3), l1_cap,
                value=value, **kw)

        jax.make_jaxpr(trace)(jax.random.PRNGKey(0),
                              jnp.ones((n,), jnp.float32))
        assert ran == [kind]

    @pytest.mark.parametrize("segment_sort", [False, True, "hash"])
    def test_wire_chunk_kernel_runs_wire_sampler_kind(self, segment_sort,
                                                      monkeypatch):
        # The host's kind (counters, bound-cache key) is the kind the
        # chunk program traces: n is the wire's row capacity fmt.cap.
        pid, pk, value = _rle_data(n=20_000)
        wire = streaming.ingest_resident_wire(pid, pk, value,
                                              num_partitions=300,
                                              n_chunks=2)
        fmt, int_clip = streaming.finish_wire_plan(
            wire.fmt, segment_sort, wire.max_run, num_partitions=300,
            row_clip_lo=0.0, row_clip_hi=5.0, linf_cap=6, l1_mode=False,
            need_flags=(True, True, False, False))
        kind = streaming.wire_sampler_kind(fmt, num_partitions=300,
                                           l1_mode=False)
        assert kind == {False: "packed", True: "tiled",
                        "hash": "hash"}[segment_sort]
        ran = []
        for name, attr in _SAMPLERS.items():
            def spy(*args, _name=name, _real=getattr(columnar, attr),
                    **kwargs):
                ran.append(_name)
                return _real(*args, **kwargs)
            monkeypatch.setattr(columnar, attr, spy)
        zeros = jnp.zeros((300,), jnp.float32)
        accs = columnar.PartitionAccumulators(*([zeros] * 5))

        def step(key, row):
            return streaming._chunk_step_rle.__wrapped__(
                key, row, int(wire.counts[0]), int(wire.n_uniq[0]), accs,
                6, 8, 0.0, 5.0, 2.5, -np.inf, np.inf, None, int_clip,
                num_partitions=300, fmt=fmt,
                need_flags=(True, True, False, False),
                int_accumulate=int_clip is not None)

        jax.make_jaxpr(step)(jax.random.PRNGKey(0),
                             jnp.asarray(wire.slab[0]))
        assert ran == [kind]
