"""Thread-parallel native encode (ISSUE 5 tentpole 1): determinism matrix.

The native worker pool processes pid-disjoint buckets concurrently
(row_packer.cc RunPool; width forced by PIPELINEDP_TPU_ENCODE_THREADS),
and the prep scan splits its counting and scatter passes over contiguous
row ranges. The contract pinned here: emitted slabs are BYTE-IDENTICAL
across thread counts {1, 4, hardware-auto} and equal to the numpy
reference encoder, for the RLE, PID_PLANES, and raw-float value wire
modes, and so are prep's bucket row counts, RLE entry counts, max run
and value plan. CI runs this file core-pinned (taskset -c 0,1) as well,
to catch any output that depends on the scheduler rather than the input.
"""

import numpy as np
import pytest

from pipelinedp_tpu.native import loader
from pipelinedp_tpu.obs import trace as trace_lib
from pipelinedp_tpu.ops import streaming, wirecodec

THREAD_MATRIX = ("1", "4", "")  # "" = auto (hardware concurrency)
# Prep also runs with more workers than cores, so a lost write or a
# misordered cursor shows however the scheduler interleaves them.
PREP_WIDTHS = THREAD_MATRIX + ("64",)
PREP_PARALLEL_ROWS = 1 << 16  # kPrepParallelRows in row_packer.cc


def _require_native():
    lib = loader.load_row_packer()
    if lib is None:
        pytest.skip("native packer unavailable")
    return lib


def _dataset(kind, n=120_000, seed=0):
    rng = np.random.default_rng(seed)
    if kind == "below_prep_threshold":
        n = PREP_PARALLEL_ROWS - 1_000
        pid = rng.integers(0, n // 12, n).astype(np.int32)
        value = rng.integers(1, 6, n).astype(np.float32)
    elif kind == "above_prep_threshold":
        n = 4 * PREP_PARALLEL_ROWS + 77  # uneven worker ranges
        pid = rng.integers(0, n // 12, n).astype(np.int32)
        value = rng.integers(1, 6, n).astype(np.float32)
    elif kind == "run_split":
        # One pid holds 70,000 rows, spread over every worker's range:
        # its run splits at 65,535 and sets max_run.
        pid = rng.integers(0, 9_000, n).astype(np.int32)
        pid[rng.choice(n, 70_000, replace=False)] = 4_321
        value = rng.integers(1, 6, n).astype(np.float32)
    elif kind == "span_above_count_budget":
        # Shifted pid span above 2^26: prep builds no count table, so
        # entry_counts is None and max_run -1.
        pid = rng.integers(0, 2_000, n).astype(np.int32)
        pid[rng.integers(0, n, 50)] = (1 << 26) + 12_345
        value = rng.integers(1, 6, n).astype(np.float32)
    elif kind == "inline_fails_rescale":
        # The 64k-row sample is whole stars (scale 1 passes the gate);
        # half stars in the last worker's range fail inline verification
        # there, and the full re-plan picks scale 0.5.
        pid = rng.integers(0, n // 12, n).astype(np.int32)
        value = rng.integers(1, 6, n).astype(np.float32)
        value[n - 5_000:] += 0.5
    elif kind == "inline_fails_range":
        # One index past 2^20 in the third worker's range: no scale
        # passes the full verification, so the re-plan ships raw floats.
        pid = rng.integers(0, n // 12, n).astype(np.int32)
        value = rng.integers(1, 6, n).astype(np.float32)
        value[n * 5 // 8] = 3.0e6
    elif kind == "rle_planes_values":
        # Repetitive ids (~12 rows/user) -> PID_RLE; integer star
        # ratings -> affine-integer value planes.
        pid = rng.integers(0, n // 12, n).astype(np.int32)
        value = rng.integers(1, 6, n).astype(np.float32)
    elif kind == "rle_raw_float":
        pid = rng.integers(0, n // 12, n).astype(np.int32)
        value = rng.uniform(0, 5, n).astype(np.float32)  # defeats planes
    elif kind == "pid_planes":
        pid = rng.permutation(n).astype(np.int32)  # unique -> PID_PLANES
        value = rng.uniform(-2, 2, n).astype(np.float32)
    else:
        raise AssertionError(kind)
    pk = rng.integers(0, 700, n).astype(np.int32)
    return pid, pk, value


def _encode_native(pid, pk, value, k, monkeypatch, threads):
    if threads:
        monkeypatch.setenv(loader.ENCODE_THREADS_ENV, threads)
    else:
        monkeypatch.delenv(loader.ENCODE_THREADS_ENV, raising=False)
    enc, info = wirecodec.make_encoder(pid, pk, value,
                                       num_partitions=700, k=k)
    if enc is None:
        pytest.skip("native encoder unavailable")
    with enc:
        cap = wirecodec._round8(int(enc.counts.max()))
        if info.pid_mode == wirecodec.PID_PLANES:
            fmt = wirecodec.WireFormat(
                bytes_pid=info.bytes_pid, bits_pk=info.bits_pk, cap=cap,
                ucap=8, value=info.plan, pid_mode=wirecodec.PID_PLANES,
                bits_pid=info.bits_pid)
            n_uniq = np.zeros(k, dtype=np.int64)
        else:
            n_uniq = enc.sort_range(0, k)
            fmt = wirecodec.WireFormat(
                bytes_pid=info.bytes_pid, bits_pk=info.bits_pk, cap=cap,
                ucap=wirecodec._round8(int(n_uniq.max())), value=info.plan)
        slab = enc.emit_range(0, k, fmt)
        return slab, np.array(enc.counts), np.array(n_uniq), fmt, info


class TestDeterminismMatrix:

    @pytest.mark.parametrize(
        "kind", ["rle_planes_values", "rle_raw_float", "pid_planes"])
    def test_slabs_identical_across_thread_counts_and_numpy(
            self, kind, monkeypatch):
        _require_native()
        pid, pk, value = _dataset(kind)
        k = 6
        slabs = {}
        fmts = {}
        for threads in THREAD_MATRIX:
            slab, counts, n_uniq, fmt, info = _encode_native(
                pid, pk, value, k, monkeypatch, threads)
            slabs[threads], fmts[threads] = slab, fmt
        ref = slabs[THREAD_MATRIX[0]]
        for threads in THREAD_MATRIX[1:]:
            assert fmts[threads] == fmts[THREAD_MATRIX[0]]
            np.testing.assert_array_equal(
                ref, slabs[threads],
                err_msg=f"thread count {threads or 'auto'} changed bytes")
        # The numpy reference is the oracle: same bytes, any width.
        ref_slab, _, _, ref_fmt = wirecodec.encode_buckets_numpy(
            pid, pk, value, pid_lo=info.pid_lo, k=k,
            bytes_pid=info.bytes_pid, bits_pk=info.bits_pk,
            plan=info.plan, pid_mode=info.pid_mode,
            bits_pid=info.bits_pid)
        assert ref_fmt == fmts[THREAD_MATRIX[0]]
        np.testing.assert_array_equal(ref, ref_slab)

    def test_pack_buckets_identical_across_thread_counts(self, monkeypatch):
        _require_native()
        rng = np.random.default_rng(1)
        n = 150_000
        pid = rng.integers(500, 90_000, n).astype(np.int32)
        pk = rng.integers(0, 3_000, n).astype(np.int32)
        value = rng.uniform(-2, 7, n).astype(np.float32)
        outs = []
        for threads in THREAD_MATRIX:
            if threads:
                monkeypatch.setenv(loader.ENCODE_THREADS_ENV, threads)
            else:
                monkeypatch.delenv(loader.ENCODE_THREADS_ENV,
                                   raising=False)
            packed = streaming._pack_native(pid, pk, value, 500, 8, 3, 2,
                                            False, 9)
            assert packed is not None
            outs.append(packed)
        for bufs, counts in outs[1:]:
            np.testing.assert_array_equal(outs[0][1], counts)
            np.testing.assert_array_equal(outs[0][0], bufs)


class TestPrepAcrossWidths:
    """pdp_rle_prep's outputs (bucket row counts, RLE entry counts, max
    run, the inline value plan) and the slabs emitted from them are the
    same at every pool width and equal to the numpy oracle, on both
    sides of the single-thread threshold and on the re-plan path."""

    REPLANNED = ("inline_fails_rescale", "inline_fails_range")

    @pytest.mark.parametrize("kind", [
        "below_prep_threshold", "above_prep_threshold", "run_split",
        "span_above_count_budget", *REPLANNED])
    def test_prep_identical_across_widths_and_numpy(self, kind,
                                                    monkeypatch):
        _require_native()
        pid, pk, value = _dataset(kind)
        k = 6
        outs = {}
        tracer = trace_lib.install(trace_lib.Tracer())
        try:
            for threads in PREP_WIDTHS:
                tracer.reset()
                outs[threads] = _encode_native(pid, pk, value, k,
                                               monkeypatch, threads)
                scans = [sp.attrs["threads"] for sp in tracer.spans()
                         if sp.name == "dp/wire_prep/native_scan"]
                # A failed inline verification scans twice (re-plan).
                assert len(scans) == (2 if kind in self.REPLANNED else 1)
                if threads in ("4", "64"):
                    wide = len(pid) >= PREP_PARALLEL_ROWS
                    want = int(threads) if wide else 1
                    assert scans == [want] * len(scans)
        finally:
            trace_lib.shutdown()
        info = outs["1"][4]
        plan, _ = wirecodec.plan_and_index(value)
        entries, max_run = wirecodec.rle_entry_stats_numpy(
            pid, info.pid_lo, k, info.pid_span)
        ref_slab, ref_counts, _, ref_fmt = wirecodec.encode_buckets_numpy(
            pid, pk, value, pid_lo=info.pid_lo, k=k,
            bytes_pid=info.bytes_pid, bits_pk=info.bits_pk, plan=plan,
            pid_mode=info.pid_mode, bits_pid=info.bits_pid)
        if kind == "span_above_count_budget":
            assert entries is None
        if kind == "run_split":
            assert max_run > 65_535
        for threads, (slab, counts, _, fmt, info) in outs.items():
            width = threads or "auto"
            assert info.plan == plan, width
            np.testing.assert_array_equal(counts, ref_counts, err_msg=width)
            if entries is None:
                assert info.entry_counts is None and info.max_run == -1
            else:
                np.testing.assert_array_equal(info.entry_counts, entries,
                                              err_msg=width)
                assert info.max_run == max_run, width
            assert fmt == ref_fmt, width
            np.testing.assert_array_equal(slab, ref_slab, err_msg=width)


class TestEncodeThreadsKnob:

    def test_env_validation(self, monkeypatch):
        monkeypatch.setenv(loader.ENCODE_THREADS_ENV, "junk")
        with pytest.raises(ValueError, match="must be an integer"):
            loader.encode_threads()
        monkeypatch.setenv(loader.ENCODE_THREADS_ENV, "65")
        with pytest.raises(ValueError, match=r"\[0, 64\]"):
            loader.encode_threads()
        monkeypatch.setenv(loader.ENCODE_THREADS_ENV, "-1")
        with pytest.raises(ValueError):
            loader.encode_threads()
        monkeypatch.setenv(loader.ENCODE_THREADS_ENV, "  8 ")
        assert loader.encode_threads() == 8
        monkeypatch.delenv(loader.ENCODE_THREADS_ENV, raising=False)
        assert loader.encode_threads() == 0

    def test_override_reaches_native(self, monkeypatch):
        lib = _require_native()
        monkeypatch.setenv(loader.ENCODE_THREADS_ENV, "5")
        assert loader.apply_encode_threads(lib) == 5
        assert lib.pdp_get_encode_threads() == 5
        monkeypatch.delenv(loader.ENCODE_THREADS_ENV, raising=False)
        assert loader.apply_encode_threads(lib) == 0
        assert lib.pdp_get_encode_threads() == 0

    def test_prefetch_and_slab_env_validation(self, monkeypatch):
        monkeypatch.setenv(streaming.PREFETCH_ENV, "9")
        with pytest.raises(ValueError, match=r"\[0, 4\]"):
            streaming.prefetch_depth()
        monkeypatch.setenv(streaming.PREFETCH_ENV, "0")
        assert streaming.prefetch_depth() == 0
        monkeypatch.delenv(streaming.PREFETCH_ENV, raising=False)
        assert streaming.prefetch_depth() == 1
        monkeypatch.setenv(streaming.SLAB_BYTES_ENV, "12")
        with pytest.raises(ValueError):
            streaming.slab_byte_budget(True)
        monkeypatch.setenv(streaming.SLAB_BYTES_ENV, str(32 << 20))
        assert streaming.slab_byte_budget(True) == 32 << 20
        assert streaming.slab_byte_budget(False) == 32 << 20
        monkeypatch.delenv(streaming.SLAB_BYTES_ENV, raising=False)
        assert (streaming.slab_byte_budget(True)
                == streaming.PIPELINED_SLAB_BYTE_BUDGET)


class TestStreamedParityAcrossThreadCounts:
    """End-to-end: the streamed accumulators are bit-identical whatever
    the encode worker width (slabs identical => kernels see identical
    bytes)."""

    def test_stream_bitwise_across_thread_counts(self, monkeypatch):
        _require_native()
        import jax
        rng = np.random.default_rng(4)
        n = 60_000
        pid = rng.integers(0, 4_000, n).astype(np.int64)
        pk = rng.integers(0, 150, n).astype(np.int32)
        value = rng.integers(1, 6, n).astype(np.float32)
        results = []
        for threads in THREAD_MATRIX:
            if threads:
                monkeypatch.setenv(loader.ENCODE_THREADS_ENV, threads)
            else:
                monkeypatch.delenv(loader.ENCODE_THREADS_ENV,
                                   raising=False)
            accs = streaming.stream_bound_and_aggregate(
                jax.random.PRNGKey(11), pid, pk, value,
                num_partitions=150, linf_cap=5, l0_cap=10,
                row_clip_lo=0.0, row_clip_hi=5.0, middle=2.5,
                group_clip_lo=-np.inf, group_clip_hi=np.inf, n_chunks=6)
            results.append([np.asarray(a) for a in accs])
        for other in results[1:]:
            for a, b in zip(results[0], other):
                np.testing.assert_array_equal(a, b)
