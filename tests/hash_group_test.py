"""Round-10 sortless hash-binned group stage: parity matrix, adversarial
distributions, overflow demotion, and sampler-identity fingerprints.

The contract pinned here (ISSUE 12 tentpole): ``segment_sort="hash"``
replaces the group stage's sort with one-pass hash binning + keyed-
priority selection. The sampled row multiset is IDENTICAL to the sorted
paths' for the same PRNG key (same salt / truncated-rand draws), and
under the order-exactness gate (``columnar.hash_exact_gate``) released
values are BIT-identical to ``segment_sort=True``/``False`` regardless
of reduction order — across {group-clip, no-clip} x {single-device,
mesh8} x {compact merge on/off}, cold, warm replay and crash-resume.
Outside the gate counts stay exact and sums are ULP-close.

Satellites pinned alongside: the bound cache keys on the RESOLVED
sampler (not the knob string), checkpoints refuse resumes produced
under a different sampler, and the overflow-demotion backstop engages
without changing a bit.
"""

import dataclasses

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
def _reset_counters():
    profiler.reset_events("ops/")
    yield


def _sampler_chunks() -> dict:
    """Executed chunk launches per sampler kind since the last reset
    (kinds that ran none are left out)."""
    counts = {kind: profiler.event_count(columnar.sampler_event(kind))
              for kind in ("hash", "tiled", "packed", "general")}
    return {kind: n for kind, n in counts.items() if n}


def _rle_data(n=60_000, n_parts=300, seed=0, integer_values=True):
    """Repetitive pids (~20 rows/user) -> PID_RLE wire with small
    max_run; integer values -> VALUE_PLANES -> the exactness gate can
    hold."""
    rng = np.random.default_rng(seed)
    pid = rng.integers(0, n // 20, n).astype(np.int64)
    pk = rng.integers(0, n_parts, n).astype(np.int32)
    if integer_values:
        value = rng.integers(0, 6, n).astype(np.float32)
    else:
        value = rng.uniform(0, 5, n).astype(np.float32)
    return pid, pk, value


def _stream(pid, pk, value, *, mesh=None, n_parts=300, has_group_clip=True,
            need_flags=(True, True, False, False), **kw):
    clips = (dict(row_clip_lo=-np.inf, row_clip_hi=np.inf, middle=0.0,
                  group_clip_lo=-30.0, group_clip_hi=30.0)
             if has_group_clip else
             dict(row_clip_lo=0.0, row_clip_hi=5.0, middle=2.5,
                  group_clip_lo=-np.inf, group_clip_hi=np.inf))
    args = (jax.random.PRNGKey(7), pid, pk, value)
    common = dict(num_partitions=n_parts, linf_cap=6, l0_cap=8,
                  has_group_clip=has_group_clip,
                  n_chunks=kw.pop("n_chunks", 8),
                  need_flags=need_flags, **clips, **kw)
    if mesh is not None:
        accs = sharded.stream_bound_and_aggregate(mesh, *args, **common)
    else:
        accs = streaming.stream_bound_and_aggregate(*args, **common)
    return jax.device_get(accs)


def _assert_bitwise(a, b):
    for name, x, y in zip(a._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)


class TestHashParityMatrix:
    """segment_sort="hash" vs the round-8 oracle, bitwise, under the
    exactness gate (integer values, COUNT/SUM/PID_COUNT columns)."""

    @pytest.mark.parametrize("has_group_clip", [True, False])
    @pytest.mark.parametrize("compact", [True, False])
    def test_rle_single_device(self, has_group_clip, compact):
        pid, pk, value = _rle_data()
        legacy = _stream(pid, pk, value, has_group_clip=has_group_clip,
                         compact_merge=compact, segment_sort=False)
        profiler.reset_events("ops/")
        hashed = _stream(pid, pk, value, has_group_clip=has_group_clip,
                         compact_merge=compact, segment_sort="hash")
        # Non-vacuous: every chunk ran the sortless stage, none a sort.
        assert _sampler_chunks() == {"hash": 8}
        assert profiler.event_count(columnar.EVENT_HASH_DEMOTIONS) == 0
        assert profiler.event_count(columnar.EVENT_HASH_OCCUPANCY) > 0
        _assert_bitwise(legacy, hashed)

    @pytest.mark.parametrize("has_group_clip", [True, False])
    @pytest.mark.parametrize("compact", [True, False])
    def test_rle_mesh8(self, mesh, has_group_clip, compact):
        pid, pk, value = _rle_data(n=40_000)
        legacy = _stream(pid, pk, value, mesh=mesh,
                         has_group_clip=has_group_clip,
                         compact_merge=compact, segment_sort=False)
        profiler.reset_events("ops/")
        hashed = _stream(pid, pk, value, mesh=mesh,
                         has_group_clip=has_group_clip,
                         compact_merge=compact, segment_sort="hash")
        assert _sampler_chunks() == {"hash": 8}
        _assert_bitwise(legacy, hashed)

    def test_hash_matches_tiled_and_packed(self):
        pid, pk, value = _rle_data(seed=3)
        tiled = _stream(pid, pk, value, segment_sort=True)
        hashed = _stream(pid, pk, value, segment_sort="hash")
        _assert_bitwise(tiled, hashed)

    def test_auto_resolves_to_hash_under_gate(self):
        # COUNT+SUM (no norm columns) over an integer grid: auto must
        # pick the sortless stage and match the forced knob bitwise.
        pid, pk, value = _rle_data(seed=4)
        profiler.reset_events("ops/")
        auto = _stream(pid, pk, value, segment_sort="auto")
        assert _sampler_chunks() == {"hash": 8}
        forced = _stream(pid, pk, value, segment_sort="hash")
        _assert_bitwise(auto, forced)

    def test_auto_declines_outside_gate(self):
        # Norm columns (MEAN/VARIANCE) are non-integer: auto must fall
        # back to the sorted dispatch even though the values are integer.
        pid, pk, value = _rle_data(seed=5)
        profiler.reset_events("ops/")
        _stream(pid, pk, value, segment_sort="auto",
                need_flags=(True, True, True, True))
        assert "hash" not in _sampler_chunks()
        # Continuous values defeat the integer grid: no gate, no hash.
        pid, pk, value = _rle_data(seed=6, integer_values=False)
        profiler.reset_events("ops/")
        _stream(pid, pk, value, segment_sort="auto")
        assert "hash" not in _sampler_chunks()

    def test_continuous_values_forced_hash_ulp_contract(self):
        # Forced outside the gate: counts/pid-counts exact, sums
        # ULP-close (different reduction order), never wrong.
        pid, pk, value = _rle_data(seed=7, integer_values=False)
        legacy = _stream(pid, pk, value, has_group_clip=False,
                         segment_sort=False)
        profiler.reset_events("ops/")
        hashed = _stream(pid, pk, value, has_group_clip=False,
                         segment_sort="hash")
        assert _sampler_chunks() == {"hash": 8}
        np.testing.assert_array_equal(np.asarray(legacy.count),
                                      np.asarray(hashed.count))
        np.testing.assert_array_equal(np.asarray(legacy.pid_count),
                                      np.asarray(hashed.pid_count))
        np.testing.assert_allclose(np.asarray(legacy.sum),
                                   np.asarray(hashed.sum),
                                   rtol=1e-5, atol=1e-4)


class TestHashAdversarial:
    """Adversarial distributions of ISSUE 12 satellite 2."""

    def test_one_pid_owns_an_entire_bucket(self):
        # One privacy id holds every row of its bucket: its segment IS
        # the bucket, so the bin width must stretch to the whole run.
        rng = np.random.default_rng(1)
        n_heavy, n_rest = 96, 400
        pid = np.concatenate([np.zeros(n_heavy, np.int64),
                              rng.integers(1, 50, n_rest)])
        pk = rng.integers(0, 40, n_heavy + n_rest).astype(np.int32)
        value = rng.integers(0, 6, n_heavy + n_rest).astype(np.float32)
        legacy = _stream(pid, pk, value, n_parts=40, segment_sort=False,
                         n_chunks=2)
        hashed = _stream(pid, pk, value, n_parts=40, segment_sort="hash",
                         n_chunks=2)
        _assert_bitwise(legacy, hashed)

    def test_all_unique_pids_planes_mode(self):
        # Near-unique pids choose the PID_PLANES wire (arrival order, no
        # pid-sorted invariant): the hash stage cannot engage and parity
        # must hold trivially through the general sampler.
        rng = np.random.default_rng(2)
        n = 20_000
        pid = rng.permutation(n).astype(np.int64)
        pk = rng.integers(0, 300, n).astype(np.int32)
        value = rng.integers(0, 6, n).astype(np.float32)
        legacy = _stream(pid, pk, value, segment_sort=False)
        profiler.reset_events("ops/")
        hashed = _stream(pid, pk, value, segment_sort="hash")
        assert _sampler_chunks() == {"general": 8}
        _assert_bitwise(legacy, hashed)

    def test_adversarial_group_hash_collisions(self, monkeypatch):
        # Force EVERY (pid, pk) group onto one hash value: group order
        # degenerates to pk order in both paths, and the pairwise
        # selection must fall back exactly like the packed sort's key
        # comparison does. Distinct shape so the jit cache cannot serve
        # a pre-patch compilation.
        monkeypatch.setattr(
            columnar, "_group_hash",
            lambda pid, pk, salt: jnp.zeros(pid.shape, jnp.uint32))
        pid, pk, value = _rle_data(n=7_777, n_parts=123, seed=8)
        legacy = _stream(pid, pk, value, n_parts=123, segment_sort=False)
        hashed = _stream(pid, pk, value, n_parts=123, segment_sort="hash")
        _assert_bitwise(legacy, hashed)

    def test_empty_and_singleton_partitions(self):
        # A huge partition vocabulary where almost every partition is
        # empty and the occupied ones hold single rows.
        rng = np.random.default_rng(3)
        n = 5_000
        pid = np.sort(rng.integers(0, n, n)).astype(np.int64)
        pk = rng.choice([0, 1, 777, 4_095], n).astype(np.int32)
        value = rng.integers(0, 6, n).astype(np.float32)
        legacy = _stream(pid, pk, value, n_parts=4_096,
                         segment_sort=False)
        hashed = _stream(pid, pk, value, n_parts=4_096,
                         segment_sort="hash")
        _assert_bitwise(legacy, hashed)

    def test_overflow_demotion_engages_without_changing_bits(self):
        # Crafted skew: bucket 0 holds ONE pid with a long run (stretches
        # the bin width, shrinking the grid's bin budget), bucket 1 holds
        # thousands of distinct pids — more segments than the budgeted
        # bins, so that chunk MUST demote to the sorted kernel while the
        # other chunk stays on the hash stage. Bits never change.
        k = 2
        cand = np.arange(0, 60_000, dtype=np.int64)
        b = ((cand.astype(np.uint32) * np.uint32(2654435761))
             >> np.uint32(16)) % np.uint32(k)
        bucket_of_zero = int(b[0])
        heavy = 0  # pid 0 keeps pid_lo == 0, so the hash is unshifted
        others = cand[(b != bucket_of_zero) & (cand != heavy)][:3_000]
        # ~8 rows per light pid: repetitive enough that the codec keeps
        # the PID_RLE (pid-sorted) wire the hash stage needs.
        pid = np.concatenate([np.full(600, heavy, np.int64),
                              np.repeat(others, 8)])
        rng = np.random.default_rng(4)
        pk = rng.integers(0, 64, len(pid)).astype(np.int32)
        value = rng.integers(0, 6, len(pid)).astype(np.float32)

        legacy = _stream(pid, pk, value, n_parts=64, segment_sort=False,
                         n_chunks=k)
        profiler.reset_events("ops/")
        hashed = _stream(pid, pk, value, n_parts=64, segment_sort="hash",
                         n_chunks=k)
        assert profiler.event_count(columnar.EVENT_HASH_DEMOTIONS) == 1
        assert _sampler_chunks()["hash"] == 1
        _assert_bitwise(legacy, hashed)

    def test_overflow_demotion_mesh8(self, mesh):
        # Mesh twin of the demotion backstop: a chunk demotes when ANY
        # of its n_dev buckets overflows the planned bins; the demoted
        # chunk runs the sorted kernel, bits unchanged.
        k = 16  # 2 chunks x 8 devices
        cand = np.arange(0, 120_000, dtype=np.int64)
        b = ((cand.astype(np.uint32) * np.uint32(2654435761))
             >> np.uint32(16)) % np.uint32(k)
        heavy = 0
        others = cand[(b != int(b[0])) & (cand != heavy)][:3_000]
        pid = np.concatenate([np.full(600, heavy, np.int64),
                              np.repeat(others, 8)])
        rng = np.random.default_rng(4)
        pk = rng.integers(0, 64, len(pid)).astype(np.int32)
        value = rng.integers(0, 6, len(pid)).astype(np.float32)
        legacy = _stream(pid, pk, value, mesh=mesh, n_parts=64,
                         segment_sort=False, n_chunks=2)
        profiler.reset_events("ops/")
        hashed = _stream(pid, pk, value, mesh=mesh, n_parts=64,
                         segment_sort="hash", n_chunks=2)
        assert profiler.event_count(columnar.EVENT_HASH_DEMOTIONS) > 0
        _assert_bitwise(legacy, hashed)

    def test_bin_overflow_backstop_empties_not_corrupts(self):
        # Direct kernel call with lying geometry (more segments than
        # bins / a run longer than the bin width — corrupt wire
        # metadata): the backstop must yield EMPTY accumulators, never a
        # silently re-sampled release.
        n = 1_024
        rng = np.random.default_rng(5)
        pid = np.sort(rng.integers(0, 100, n)).astype(np.int32)
        pk = rng.integers(0, 64, n).astype(np.int32)
        value = np.ones(n, dtype=np.float32)
        valid = np.ones(n, dtype=bool)
        out = jax.device_get(columnar.bound_and_aggregate(
            jax.random.PRNGKey(11), pid, pk, value, valid,
            num_partitions=64, linf_cap=3, l0_cap=4,
            row_clip_lo=0.0, row_clip_hi=5.0, middle=2.5,
            group_clip_lo=-np.inf, group_clip_hi=np.inf,
            pid_sorted=True, max_segments=1 << 10,
            hash_bins=8, hash_bin_rows=8))
        assert float(np.asarray(out.count).sum()) == 0.0
        assert float(np.asarray(out.pid_count).sum()) == 0.0


class TestHashKernelUnit:
    """Direct columnar-level parity of the hash-binned stage."""

    def _sorted_rows(self, n=8_192, n_parts=64, seed=2, runs=12):
        rng = np.random.default_rng(seed)
        pid = np.sort(rng.integers(0, n // runs, n)).astype(np.int32)
        pk = rng.integers(0, n_parts, n).astype(np.int32)
        value = rng.integers(0, 6, n).astype(np.float32)
        valid = np.arange(n) < (n - 100)  # padded tail
        return pid, pk, value, valid

    def _geometry(self, pid, valid):
        per = np.bincount(pid[valid])
        w = max(8, (int(per.max()) + 7) & ~7)
        bins = max(8, (int((per > 0).sum()) + 7) & ~7)
        return bins, w

    def _kernel(self, pid, pk, value, valid, n_parts, **kw):
        return jax.device_get(columnar.bound_and_aggregate(
            jax.random.PRNGKey(11), pid, pk, value, valid,
            num_partitions=n_parts, linf_cap=3, l0_cap=4,
            row_clip_lo=0.0, row_clip_hi=5.0, middle=2.5,
            group_clip_lo=-np.inf, group_clip_hi=np.inf,
            need_norm=False, need_norm_sq=False,
            pid_sorted=True, max_segments=1 << 11, **kw))

    def test_hash_bitwise_equals_packed_and_tiled(self):
        pid, pk, value, valid = self._sorted_rows()
        bins, w = self._geometry(pid, valid)
        max_run = int(np.bincount(pid[valid]).max())
        base = self._kernel(pid, pk, value, valid, 64)
        tiled = self._kernel(pid, pk, value, valid, 64,
                             tile_rows=1024, tile_slack=max_run)
        hashed = self._kernel(pid, pk, value, valid, 64,
                              hash_bins=bins, hash_bin_rows=w)
        _assert_bitwise(base, hashed)
        _assert_bitwise(tiled, hashed)

    def test_row_mask_replays_hash(self):
        # The row-mask kernel with the same hash statics must make
        # exactly the sorted samplers' decisions (quantile replay
        # contract).
        pid, pk, value, valid = self._sorted_rows()
        bins, w = self._geometry(pid, valid)
        key = jax.random.PRNGKey(11)
        base = columnar.bound_row_mask(
            key, pid, pk, valid, 3, 4, pid_sorted=True,
            max_segments=1 << 11, num_partitions=64)
        hashed = columnar.bound_row_mask(
            key, pid, pk, valid, 3, 4, pid_sorted=True,
            max_segments=1 << 11, num_partitions=64,
            hash_bins=bins, hash_bin_rows=w)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(hashed))

    def test_compact_bitwise_under_gate(self):
        # Compact emission reuses PR 5's merge shapes: folding the hash
        # path's CompactGroups must release the same bits as the sorted
        # compact path (exact-integer columns).
        pid, pk, value, valid = self._sorted_rows(seed=9)
        bins, w = self._geometry(pid, valid)
        kw = dict(num_partitions=64, max_groups=512, linf_cap=3, l0_cap=4,
                  row_clip_lo=0.0, row_clip_hi=5.0, middle=2.5,
                  group_clip_lo=-20.0, group_clip_hi=20.0,
                  need_norm=False, need_norm_sq=False,
                  pid_sorted=True, max_segments=1 << 11)
        key = jax.random.PRNGKey(4)
        base = columnar.bound_and_aggregate_compact(
            key, pid, pk, value, valid, **kw)
        hashed = columnar.bound_and_aggregate_compact(
            key, pid, pk, value, valid, hash_bins=bins, hash_bin_rows=w,
            **kw)
        zero = columnar.PartitionAccumulators(
            *(jnp.zeros((64,), jnp.float32) for _ in range(5)))

        def fold(cg):
            stacked = [jnp.stack([cg[i]]) for i in range(6)]
            return jax.device_get(columnar.merge_compact_chunks(
                zero, *stacked, num_partitions=64,
                need_flags=(True, True, False, False)))

        assert int(jax.device_get(base.n_kept)) == int(
            jax.device_get(hashed.n_kept))
        _assert_bitwise(fold(base), fold(hashed))


class TestHashGateAndPlanning:
    def test_hash_exact_gate(self):
        ok = columnar.hash_exact_gate(0.0, 1.0, 3, 0.0, 5.0, 6,
                                      -np.inf, np.inf, 1 << 15)
        assert ok
        # Integer finite group clips pass; fractional ones fail.
        assert columnar.hash_exact_gate(0.0, 1.0, 3, 0.0, 5.0, 6,
                                        -30.0, 30.0, 1 << 15)
        assert not columnar.hash_exact_gate(0.0, 1.0, 3, 0.0, 5.0, 6,
                                            -30.5, 30.0, 1 << 15)
        # NaN group clip fails.
        assert not columnar.hash_exact_gate(0.0, 1.0, 3, 0.0, 5.0, 6,
                                            np.nan, 30.0, 1 << 15)
        # Partition-fold exactness: cap * max bound must stay < 2^24.
        assert not columnar.hash_exact_gate(0.0, 1.0, 3, 0.0, 5.0, 6,
                                            -np.inf, np.inf, 1 << 24)
        assert not columnar.hash_exact_gate(0.0, 1.0, 3, 0.0, 5.0, 6,
                                            -np.inf, np.inf,
                                            (1 << 24) // 5 + 1)
        # A huge finite clip can RAISE the partition bound past 2^24.
        assert not columnar.hash_exact_gate(0.0, 1.0, 3, 0.0, 5.0, 6,
                                            0.0, float(1 << 23), 1 << 15)
        # The int plan itself failing (fractional grid) fails the gate.
        assert not columnar.hash_exact_gate(0.0, 0.5, 3, 0.0, 5.0, 6,
                                            -np.inf, np.inf, 1 << 15)
        # Traced / non-concrete cap fails closed.
        assert not columnar.hash_exact_gate(0.0, 1.0, 3, 0.0, 5.0, 6,
                                            -np.inf, np.inf, None)

    def _fmt(self, cap=1 << 15, ucap=1 << 12,
             pid_mode=wirecodec.PID_RLE):
        return wirecodec.WireFormat(
            bytes_pid=3, bits_pk=10, cap=cap, ucap=ucap,
            value=wirecodec.ValuePlan(wirecodec.VALUE_PLANES, 0.0, 1.0, 3),
            pid_mode=pid_mode)

    def test_plan_group_binning_forced_and_auto(self):
        fmt = self._fmt()
        forced = wirecodec.plan_group_binning(fmt, "hash", 16)
        assert forced.hash_bins >= fmt.ucap and forced.hash_bin_rows == 16
        # auto requires the exactness gate...
        assert wirecodec.plan_group_binning(fmt, "auto", 16).hash_bins == 0
        auto = wirecodec.plan_group_binning(fmt, "auto", 16, exact=True)
        assert auto.hash_bins == forced.hash_bins
        # ...and True (tiling) never plans bins.
        assert wirecodec.plan_group_binning(fmt, True, 16).hash_bins == 0

    def test_plan_group_binning_declines(self):
        fmt = self._fmt()
        # No/unknown max_run, disabled knob, planes wire.
        assert wirecodec.plan_group_binning(fmt, "hash", -1).hash_bins == 0
        assert wirecodec.plan_group_binning(fmt, "hash", 0).hash_bins == 0
        assert wirecodec.plan_group_binning(fmt, False, 16).hash_bins == 0
        planes = self._fmt(pid_mode=wirecodec.PID_PLANES)
        assert wirecodec.plan_group_binning(planes, "hash",
                                            16).hash_bins == 0
        # Bin width ceilings: auto declines above HASH_MAX_BIN_ROWS,
        # forced above the forced ceiling.
        wide = wirecodec.plan_group_binning(fmt, "auto", 200, exact=True)
        assert wide.hash_bins == 0
        assert wirecodec.plan_group_binning(fmt, "hash", 200).hash_bins > 0
        assert wirecodec.plan_group_binning(fmt, "hash",
                                            2_000).hash_bins == 0
        # auto never plans a grid some chunks would overflow (ucap above
        # the grid budget); forced accepts the budgeted bins.
        crowded = self._fmt(cap=1 << 12, ucap=1 << 12)
        assert wirecodec.plan_group_binning(crowded, "auto", 64,
                                            exact=True).hash_bins == 0
        f = wirecodec.plan_group_binning(crowded, "hash", 64)
        assert 0 < f.hash_bins < crowded.ucap

    def test_resolved_sampler_desc(self):
        fmt = self._fmt()
        kw = dict(num_partitions=1 << 10, row_clip_lo=0.0, row_clip_hi=5.0,
                  linf_cap=6, l1_mode=False, group_clip_lo=-np.inf,
                  group_clip_hi=np.inf,
                  need_flags=(True, True, False, False))
        auto = streaming.resolved_sampler_desc(fmt, "auto", 16, **kw)
        forced = streaming.resolved_sampler_desc(fmt, "hash", 16, **kw)
        legacy = streaming.resolved_sampler_desc(fmt, False, 16, **kw)
        tiled = streaming.resolved_sampler_desc(fmt, True, 16, **kw)
        # Same resolved kernel and geometry -> same identity; otherwise
        # different identities (the satellite-1 contract). auto plans no
        # tiles, so it differs from forced hash only by forced's tiled
        # demotion target.
        assert auto.startswith("hash:")
        assert auto == forced.replace("tile_rows=1024, tile_slack=16",
                                      "tile_rows=0, tile_slack=0")
        assert legacy != forced and tiled != forced and auto != forced
        # auto outside the gate (norm columns) resolves to a sorted kind.
        norm = streaming.resolved_sampler_desc(
            fmt, "auto", 16, **{**kw,
                                "need_flags": (True, True, True, True)})
        assert not norm.startswith("hash:")

    def test_resolved_sampler_desc_strings(self):
        # The bound-cache key of each sampler kind, pinned literally: a
        # change here invalidates every cached accumulator.
        kw = dict(num_partitions=1 << 10, row_clip_lo=0.0, row_clip_hi=5.0,
                  linf_cap=6, l1_mode=False, group_clip_lo=-np.inf,
                  group_clip_hi=np.inf,
                  need_flags=(True, True, False, False))
        head = ("WireFormat(bytes_pid=3, bits_pk=10, cap=32768, ucap=4096, "
                "value=ValuePlan(mode=1, bits=0.0, lo=1.0, scale=3), ")
        tiles = "tile_rows=1024, tile_slack=16, "
        fmt = self._fmt()
        cases = {
            "hash": (fmt, "hash", kw,
                     "pid_mode=0, bits_pid=0, " + tiles + "hash_bins=4096, "
                     "hash_bin_rows=16, sort_value_narrow=True)"),
            "tiled": (fmt, True, kw,
                      "pid_mode=0, bits_pid=0, " + tiles + "hash_bins=0, "
                      "hash_bin_rows=0, sort_value_narrow=True)"),
            "packed": (fmt, False, kw,
                       "pid_mode=0, bits_pid=0, tile_rows=0, tile_slack=0, "
                       "hash_bins=0, hash_bin_rows=0, "
                       "sort_value_narrow=False)"),
            "general": (self._fmt(pid_mode=wirecodec.PID_PLANES), True, kw,
                        "pid_mode=1, bits_pid=0, tile_rows=0, "
                        "tile_slack=0, hash_bins=0, hash_bin_rows=0, "
                        "sort_value_narrow=True)"),
        }
        for kind, (f, knob, args, tail) in cases.items():
            assert (streaming.resolved_sampler_desc(f, knob, 16, **args)
                    == f"{kind}:{head}{tail}"), kind
        # L1 mode keeps a pid-sorted wire on the general sampler.
        assert streaming.resolved_sampler_desc(
            fmt, True, 16, **{**kw, "l1_mode": True}) == (
                f"general:{head}pid_mode=0, bits_pid=0, {tiles}hash_bins=0, "
                "hash_bin_rows=0, sort_value_narrow=True)")


class TestSamplerFingerprints:
    """Satellite 1: flipping segment_sort can never alias a cached
    accumulator or resume a checkpoint from a different sampler."""

    def _session(self, **kw):
        rng = np.random.default_rng(6)
        n = 30_000
        data = pdp.ColumnarData(
            pid=rng.integers(0, n // 20, n).astype(np.int64),
            pk=rng.integers(0, 64, n).astype(np.int32),
            value=rng.integers(0, 6, n).astype(np.float32))
        from pipelinedp_tpu import serving
        return serving.DatasetSession(
            data, public_partitions=list(range(64)), **kw)

    def _engine_query(self, session, segment_sort, seed=3):
        accountant = pdp.NaiveBudgetAccountant(1e9, 1 - 1e-9)
        engine = pdp.JaxDPEngine(accountant, seed=seed,
                                 secure_host_noise=False,
                                 stream_chunks=session.n_chunks,
                                 segment_sort=segment_sort)
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=8,
            max_contributions_per_partition=6,
            min_value=0.0, max_value=5.0)
        result = engine.aggregate(session, params,
                                  public_partitions=list(range(64)))
        accountant.compute_budgets()
        return result.to_columns()

    def test_bound_cache_keys_on_resolved_sampler(self):
        from pipelinedp_tpu.serving import session as session_mod
        session = self._session()
        try:
            h0 = profiler.event_count(session_mod.EVENT_BOUND_HITS)
            m0 = profiler.event_count(session_mod.EVENT_BOUND_MISSES)
            a = self._engine_query(session, "auto")
            # Same seed, same resolved sampler (auto resolves to hash for
            # COUNT+SUM on this wire): HIT.
            self._engine_query(session, "auto")
            assert profiler.event_count(
                session_mod.EVENT_BOUND_HITS) == h0 + 1
            # Forced hash carries a tiled demotion target that auto does
            # not plan: another geometry, so a MISS — with the same bits.
            b = self._engine_query(session, "hash")
            for name in a:
                np.testing.assert_array_equal(a[name], b[name],
                                              err_msg=name)
            # Different resolved sampler (the round-8 oracle): MISS —
            # a hash-produced accumulator is never aliased across
            # samplers, even though the released bits agree under the
            # gate.
            self._engine_query(session, False)
            assert profiler.event_count(
                session_mod.EVENT_BOUND_HITS) == h0 + 1
            assert profiler.event_count(
                session_mod.EVENT_BOUND_MISSES) == m0 + 3
        finally:
            session.close()

    def test_checkpoint_refuses_other_sampler_resume(self):
        pid, pk, value = _rle_data()
        store = runtime.InMemoryCheckpointStore()
        policy = runtime.CheckpointPolicy(store=store, run_id="hashfp",
                                          delete_on_success=False)
        full = _stream(pid, pk, value, segment_sort="hash")
        _stream(pid, pk, value, segment_sort="hash",
                resilience=runtime.StreamResilience(
                    checkpoint_policy=policy))
        checkpoint = store.load("hashfp")
        assert 0 < checkpoint.next_chunk < checkpoint.n_chunks
        # A checkpoint produced under the hash sampler must refuse a
        # resume under any other resolved sampler...
        with pytest.raises(runtime.CheckpointMismatchError):
            _stream(pid, pk, value, segment_sort=False,
                    resume_from=checkpoint)
        with pytest.raises(runtime.CheckpointMismatchError):
            _stream(pid, pk, value, segment_sort=True,
                    resume_from=checkpoint)
        # ...and resume bit-identically under its own.
        resumed = _stream(pid, pk, value, segment_sort="hash",
                          resume_from=checkpoint)
        _assert_bitwise(full, resumed)


class TestHashWarmAndResumeParity:
    """Cold / warm-replay / crash-resume all pinned bitwise (the
    acceptance matrix of ISSUE 12)."""

    def test_warm_replay_matches_cold_single_device(self):
        pid, pk, value = _rle_data(seed=10)
        cold = _stream(pid, pk, value, segment_sort="hash")
        wire = streaming.ingest_resident_wire(pid, pk, value,
                                              num_partitions=300,
                                              n_chunks=8)
        warm = jax.device_get(streaming.replay_resident_wire(
            jax.random.PRNGKey(7), wire, linf_cap=6, l0_cap=8,
            row_clip_lo=-np.inf, row_clip_hi=np.inf, middle=0.0,
            group_clip_lo=-30.0, group_clip_hi=30.0,
            need_flags=(True, True, False, False),
            segment_sort="hash"))
        _assert_bitwise(cold, warm)

    def test_warm_replay_matches_cold_mesh8(self, mesh):
        pid, pk, value = _rle_data(n=40_000, seed=11)
        cold = _stream(pid, pk, value, mesh=mesh, segment_sort="hash")
        wire = streaming.ingest_resident_wire(
            pid, pk, value, num_partitions=300,
            n_chunks=8, n_dev=mesh.devices.size)
        warm = jax.device_get(sharded.replay_resident_wire(
            mesh, jax.random.PRNGKey(7), wire, linf_cap=6, l0_cap=8,
            row_clip_lo=-np.inf, row_clip_hi=np.inf, middle=0.0,
            group_clip_lo=-30.0, group_clip_hi=30.0,
            need_flags=(True, True, False, False),
            segment_sort="hash"))
        _assert_bitwise(cold, warm)

    def test_crash_resume_through_engine(self):
        pid, pk, value = _rle_data(seed=12)
        n_parts = 300

        def run(**engine_kw):
            accountant = pdp.NaiveBudgetAccountant(1e9, 1 - 1e-9)
            engine = pdp.JaxDPEngine(accountant, seed=3, stream_chunks=8,
                                     secure_host_noise=False,
                                     segment_sort="hash", **engine_kw)
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
        policy = runtime.CheckpointPolicy(store=store, run_id="hashkill")
        with pytest.raises(runtime.HostCrash):
            run(checkpoint_policy=policy,
                fault_injector=runtime.FaultInjector(
                    [runtime.FaultSpec("host_crash", at_slab=1)]))
        assert store.load("hashkill").next_chunk > 0
        resumed = run(checkpoint_policy=policy)
        for name in clean:
            np.testing.assert_array_equal(clean[name], resumed[name],
                                          err_msg=name)

    def test_session_warm_query_matches_cold_engine(self):
        rng = np.random.default_rng(13)
        n = 30_000
        data = pdp.ColumnarData(
            pid=rng.integers(0, n // 20, n).astype(np.int64),
            pk=rng.integers(0, 64, n).astype(np.int32),
            value=rng.integers(0, 6, n).astype(np.float32))
        from pipelinedp_tpu import serving
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
            max_partitions_contributed=8,
            max_contributions_per_partition=6,
            min_value=0.0, max_value=5.0)
        session = serving.DatasetSession(
            data, public_partitions=list(range(64)),
            segment_sort="hash", secure_host_noise=False)
        try:
            warm = session.query(params, epsilon=1e9, delta=1 - 1e-9,
                                 seed=5).to_columns()
            accountant = pdp.NaiveBudgetAccountant(1e9, 1 - 1e-9)
            engine = pdp.JaxDPEngine(accountant, seed=5,
                                     secure_host_noise=False,
                                     stream_chunks=session.n_chunks,
                                     segment_sort="hash")
            result = engine.aggregate(data, params,
                                      public_partitions=list(range(64)))
            accountant.compute_budgets()
            cold = result.to_columns()
            for name in cold:
                np.testing.assert_array_equal(cold[name], warm[name],
                                              err_msg=name)
        finally:
            session.close()


class TestQuantileHashReplay:
    """PERCENTILE rides the streamed kernels: the row mask must replay
    the SAME hash-binned sampling as the aggregation kernel, keeping
    released quantiles bitwise invariant to the knob."""

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
        hashed = self._run("hash")
        for name in legacy:
            np.testing.assert_array_equal(legacy[name], hashed[name],
                                          err_msg=name)
