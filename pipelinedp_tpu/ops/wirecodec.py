"""Lossless wire codec for the host->device row columns.

The headline input is ~1.2 GB of raw columns per aggregate (100M rows of
pid, pk and value). This module shrinks the bytes that cross the
host->device link *losslessly* by exploiting the structure the
byte-packed layout ignores. Whether the transfer or the kernel bounds a
PCIe-attached chip is not measured yet (PERF.md, open questions):

  * privacy ids repeat (~rows/users times each). Rows are stably sorted by
    pid inside each pid-disjoint bucket, so the pid column becomes a
    run-length list (unique id + uint16 run length; runs longer than 65535
    are split). 3 bytes/row -> ~0.3 bits/row at the benchmark shape.
  * partition keys are dense ids in [0, P): they need exactly
    ceil(log2(P)) bits, not a whole number of bytes. They ship as LSB-first
    bit-planes (bit j of 8 consecutive rows per byte) and are rebuilt on
    device with shifts and ors only — no gathers.
  * values are frequently discrete (the reference's north-star workload is
    movie ratings — /root/reference/examples/movie_view_ratings/
    run_without_frameworks.py: integer star ratings). `plan_value_encoding`
    detects an exact affine-integer representation v = lo + idx * scale,
    VERIFIES bit-exact float32 round-trip on the host, and ships idx as
    bit-planes. Values that fail the check ship as raw float32 (or float16
    under the existing lossy opt-in) — the codec never loses bits.

Everything for one bucket is flattened into a single row of a [k, W] uint8
slab, so a slab still ships as ONE device_put (each put pays a fixed
dispatch cost — see streaming.py).

Decode is elementwise + one cumsum + one small gather per bucket, far below
the kernel cost, and overlaps the next slab's transfer like the kernel does.

Host encode has two implementations that produce bit-identical buffers: the
multithreaded C++ packer (native/row_packer.cc, pdp_pack_buckets_rle) and
the numpy reference below (used as fallback and as the test oracle).

Role vs the reference: this is the TPU answer to the loader/shuffle layer
the reference delegates to Beam/Spark native runners
(pipeline_backend.py:38-195) — columnar, entropy-aware, and exact.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pipelinedp_tpu import profiler
from pipelinedp_tpu.obs import trace as obs_trace

# Same Knuth multiplicative hash as streaming.py's bucketing (buckets must
# stay pid-disjoint and identical across the codec and the legacy packer).
_HASH_MULT = np.uint32(2654435761)

# Value transfer modes (wire format tag; also the C++ ABI contract).
VALUE_NONE = 0  # COUNT-style: no value bytes on the wire
VALUE_PLANES = 1  # affine-integer bit-planes (lossless, host-verified)
VALUE_F32 = 2  # raw little-endian float32
VALUE_F16 = 3  # raw float16 (lossy ingest, existing opt-in)

# Privacy-id wire modes. PID_RLE requires the host radix sort (rows arrive
# on device pid-sorted per bucket — the load-bearing invariant the fused
# kernel's presorted sampler exploits); PID_PLANES ships the shifted ids as
# LSB-first bit-planes in arrival order and skips the host sort entirely —
# chosen when the RLE gain is small (near-unique ids), where the planes are
# BOTH fewer bytes and zero host sort (the device kernel sorts anyway).
PID_RLE = 0
PID_PLANES = 1

_MAX_VALUE_BITS = 20  # beyond ~1M distinct levels the planes stop paying
_RUN_SPLIT = 65535  # uint16 run-length limit; longer runs split


@dataclasses.dataclass(frozen=True)
class ValuePlan:
    """How the value column ships. lo/scale only meaningful for PLANES."""
    mode: int
    bits: int = 0
    lo: float = 0.0
    scale: float = 1.0


@dataclasses.dataclass(frozen=True)
class WireFormat:
    """Static shape/layout info shared by encoder and decoder.

    All fields are jit-static: one compile serves every bucket of a call.
    pid_mode PID_RLE lays out [uniq ids | uint16 runs | pk planes | value];
    PID_PLANES lays out [pid planes | pk planes | value] (bits_pid planes,
    arrival order, no sortedness guarantee).

    tile_rows/tile_slack describe the bucketed segment-local sort the
    kernel may run over the decoded rows (columnar tiled sampler): tiles
    of tile_rows rows, slack >= the longest single-pid run in any bucket
    (known on host from the prep-time per-pid counts). They are sort
    GEOMETRY, not wire layout — the byte offsets above are unaffected, and
    per-bucket tile offsets are derived on device in one pass from the
    RLE segment starts (offset arrays could not ride this dataclass: it
    must stay hashable/jit-static). 0 = untiled (global packed sort).
    """
    bytes_pid: int
    bits_pk: int
    cap: int  # padded rows per bucket, multiple of 8
    ucap: int  # padded RLE entries per bucket (PID_RLE only)
    value: ValuePlan
    pid_mode: int = PID_RLE
    bits_pid: int = 0  # pid bit-planes per row (PID_PLANES only)
    tile_rows: int = 0  # segment-local sort tile width (0 = untiled)
    tile_slack: int = 0  # per-tile slack >= max single-pid run
    # Sortless hash-binned group stage (segment_sort="hash";
    # plan_group_binning): per-segment bin count and bin width. Like the
    # tile fields this is kernel geometry, not wire layout; 0 = off.
    # Chunks whose RLE entry count exceeds hash_bins are demoted to the
    # tiled kernel per chunk by the drivers (never wrong bits).
    hash_bins: int = 0
    hash_bin_rows: int = 0  # bin width >= max single-pid run
    # VALUE_PLANES chunks ride the kernel sort as the narrow plane index
    # (widened to float32 after it — bit-identical releases). False
    # restores the round-8 widen-at-decode kernel; like the tile fields
    # this is kernel geometry, not wire layout (segment_sort=False).
    sort_value_narrow: bool = True

    @property
    def cap_bytes(self) -> int:
        return self.cap // 8

    @property
    def pid_sorted(self) -> bool:
        """Whether decoded rows are pid-sorted (the presorted-kernel
        invariant): structural for PID_RLE, never for PID_PLANES."""
        return self.pid_mode == PID_RLE

    @property
    def _offsets(self) -> Tuple[int, int, int, int]:
        if self.pid_mode == PID_PLANES:
            o_cnt = self.bits_pid * self.cap_bytes
            o_pk = o_cnt
        else:
            o_cnt = self.ucap * self.bytes_pid
            o_pk = o_cnt + self.ucap * 2
        o_val = o_pk + self.bits_pk * self.cap_bytes
        if self.value.mode == VALUE_PLANES:
            end = o_val + self.value.bits * self.cap_bytes
        elif self.value.mode == VALUE_F32:
            end = o_val + self.cap * 4
        elif self.value.mode == VALUE_F16:
            end = o_val + self.cap * 2
        else:
            end = o_val
        return o_cnt, o_pk, o_val, end

    @property
    def width(self) -> int:
        """Bytes per bucket row of the flat slab."""
        return self._offsets[3]


_SCALE_LADDER = (1.0, 0.5, 0.25, 0.125, 0.1, 0.05, 0.025, 0.01)


def _plan_preamble(value, value_f16):
    """Shared trivial-case handling. Returns (final_plan, None, ...) when
    the mode is decided without looking at scales, else
    (None, value_f32, lo, lo64, sample)."""
    if value is None:
        return ValuePlan(VALUE_NONE), None, None, None, None
    if value_f16:
        return ValuePlan(VALUE_F16), None, None, None, None
    value = np.asarray(value, dtype=np.float32)
    if value.size == 0:
        return ValuePlan(VALUE_F32), None, None, None, None
    lo64 = float(np.min(value))
    if not math.isfinite(lo64):
        return ValuePlan(VALUE_F32), None, None, None, None
    return None, value, np.float32(lo64), lo64, value[:65536]


def _gated_scales(sample, lo, lo64):
    """Scales from the ladder that pass the cheap 64k-sample gate (range
    check + bit-exact float32 reconstruction on the sample)."""
    for scale in _SCALE_LADDER:
        s = np.float32(scale)
        sidx = np.rint((sample.astype(np.float64) - lo64) / scale)
        if (sidx.max(initial=0.0) >= (1 << _MAX_VALUE_BITS)
                or sidx.min(initial=0.0) < 0):
            continue
        if np.array_equal(lo + sidx.astype(np.float32) * s, sample):
            yield scale, s


def plan_and_index(value: Optional[np.ndarray],
                   value_f16: bool = False
                   ) -> Tuple[ValuePlan, Optional[np.ndarray]]:
    """Chooses the value wire mode, verifying losslessness on the host.

    Tries v = lo + idx * scale for scale in a small dyadic/decimal ladder
    (a cheap sample-first check gates the full-array verification). The
    reconstruction check is done in float32 with the exact expression the
    device uses, so PLANES is bit-exact by construction. NaN/inf anywhere
    falls through to raw (NaN != NaN fails the check).

    Returns (plan, idx int32 array when plan is PLANES else None) — the
    index is computed once here and reused by the encoders (this host is
    single-pass-precious: one core, see BASELINE.md).
    """
    final, value, lo, lo64, sample = _plan_preamble(value, value_f16)
    if final is not None:
        return final, None
    for scale, s in _gated_scales(sample, lo, lo64):
        idx = _verified_index(value, lo, s, lo64, scale)
        if idx is not None:
            bits = max(1, int(idx.max(initial=0)).bit_length())
            return (ValuePlan(VALUE_PLANES, bits=bits, lo=float(lo),
                              scale=float(s)), idx)
    return ValuePlan(VALUE_F32), None


def _verified_index(value: np.ndarray, lo: np.float32, s: np.float32,
                    lo64: float, scale: float) -> Optional[np.ndarray]:
    """idx with lo + idx*scale == value verified bit-exact, or None.

    Chunked: the float64 intermediates live per-chunk (a full-array pass
    at 100M rows allocates multiple 800 MB temporaries and was measured
    ~6x slower than this on the single-core bench host).
    """
    n = len(value)
    out = np.empty(n, dtype=np.int32)
    step = 1 << 22
    for c0 in range(0, n, step):
        chunk = value[c0:c0 + step]
        idx = np.rint((chunk.astype(np.float64) - lo64) / scale)
        if (idx.max(initial=0.0) >= (1 << _MAX_VALUE_BITS)
                or idx.min(initial=0.0) < 0):
            return None
        idx32 = idx.astype(np.int32)
        if not np.array_equal(lo + idx32.astype(np.float32) * s, chunk):
            return None
        out[c0:c0 + step] = idx32
    return out


def plan_value_encoding(value: Optional[np.ndarray],
                        value_f16: bool = False) -> ValuePlan:
    """plan_and_index without the index (compatibility surface)."""
    return plan_and_index(value, value_f16)[0]


def _pack_le(out: np.ndarray, col: np.ndarray, nbytes: int) -> None:
    """Little-endian byte split of an int column into out[:, :nbytes]."""
    col = col.astype(np.uint32, copy=False)
    for b in range(nbytes):
        out[:, b] = (col >> np.uint32(8 * b)).astype(np.uint8)


def _pack_planes(out: np.ndarray, col: np.ndarray, bits: int) -> None:
    """LSB-first bit-planes: out[j, r >> 3] bit (r & 7) = bit j of col[r].

    out: [bits, cap // 8] uint8 (zeroed); col: [m] nonneg ints, m <= cap.
    """
    m = len(col)
    if m == 0:
        return
    col = col.astype(np.uint32, copy=False)
    cap8 = out.shape[1]
    for j in range(bits):
        bitvals = ((col >> np.uint32(j)) & np.uint32(1)).astype(np.uint8)
        padded = np.zeros(cap8 * 8, dtype=np.uint8)
        padded[:m] = bitvals
        # LSB-first within each byte (np.packbits is MSB-first -> bitorder).
        out[j, :] = np.packbits(padded, bitorder="little")


def encode_buckets_numpy(
    pid: np.ndarray,
    pk: np.ndarray,
    value: Optional[np.ndarray],
    *,
    pid_lo: int,
    k: int,
    bytes_pid: int,
    bits_pk: int,
    plan: ValuePlan,
    pid_mode: int = PID_RLE,
    bits_pid: int = 0,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, WireFormat]:
    """Numpy reference encoder. Returns (slab [k, W] uint8, n_rows [k],
    n_uniq [k], fmt). Bit-identical to the native packer's output.

    pid_mode PID_PLANES ships the shifted pid column as bits_pid bit-planes
    with rows grouped (stably) by the pid low byte — the same arrival order
    the native prep scatter produces, so the two encoders stay
    bit-identical in this mode too.
    """
    n = len(pid)
    shifted = (np.asarray(pid) - pid_lo).astype(np.uint32, copy=False)
    bucket = ((shifted * _HASH_MULT) >> np.uint32(16)) % np.uint32(k)
    counts = np.bincount(bucket, minlength=k).astype(np.int64)
    cap = _round8(int(counts.max()) if n else 8)

    vidx = None
    if plan.mode == VALUE_PLANES:
        vidx = np.rint(
            (np.asarray(value, dtype=np.float64) - float(plan.lo))
            / float(plan.scale)).astype(np.int64)

    if pid_mode == PID_PLANES:
        fmt = WireFormat(bytes_pid=bytes_pid, bits_pk=bits_pk, cap=cap,
                         ucap=8, value=plan, pid_mode=PID_PLANES,
                         bits_pid=bits_pid)
        slab = np.zeros((k, fmt.width), dtype=np.uint8)
        o_cnt, o_pk, o_val, _ = fmt._offsets
        for c in range(k):
            rows = np.flatnonzero(bucket == c)
            # Match the native prep scatter order (radix pass 0): stable
            # grouping by the pid low byte.
            order = rows[np.argsort(shifted[rows] & np.uint32(0xFF),
                                    kind="stable")]
            row = slab[c]
            pid_planes = row[:o_cnt].reshape(bits_pid, fmt.cap_bytes)
            _pack_planes(pid_planes, shifted[order], bits_pid)
            _emit_pk_and_value(row, fmt, plan, np.asarray(pk), value, vidx,
                               order, o_pk, o_val)
        return slab, counts, np.zeros(k, dtype=np.int64), fmt

    # Pass 1: per-bucket stable pid sort + RLE to size ucap exactly.
    orders, uniq_cols, cnt_cols = [], [], []
    for c in range(k):
        rows = np.flatnonzero(bucket == c)
        order = rows[np.argsort(shifted[rows], kind="stable")]
        orders.append(order)
        u, cts = _rle_split(shifted[order])
        uniq_cols.append(u)
        cnt_cols.append(cts)
    n_uniq = np.array([len(u) for u in uniq_cols], dtype=np.int64)
    ucap = _round8(int(n_uniq.max()) if n else 8)
    fmt = WireFormat(bytes_pid=bytes_pid, bits_pk=bits_pk, cap=cap,
                     ucap=ucap, value=plan)

    slab = np.zeros((k, fmt.width), dtype=np.uint8)
    o_cnt, o_pk, o_val, _ = fmt._offsets
    for c in range(k):
        order, u, cts = orders[c], uniq_cols[c], cnt_cols[c]
        row = slab[c]
        _pack_le(row[:len(u) * bytes_pid].reshape(-1, bytes_pid), u,
                 bytes_pid)
        _pack_le(row[o_cnt:o_cnt + len(cts) * 2].reshape(-1, 2), cts, 2)
        _emit_pk_and_value(row, fmt, plan, np.asarray(pk), value, vidx,
                           order, o_pk, o_val)
    return slab, counts, n_uniq, fmt


def _emit_pk_and_value(row, fmt, plan, pk, value, vidx, order, o_pk,
                       o_val) -> None:
    """Shared pk-planes + value tail of both numpy bucket layouts."""
    pk_planes = row[o_pk:o_pk + fmt.bits_pk * fmt.cap_bytes].reshape(
        fmt.bits_pk, fmt.cap_bytes)
    _pack_planes(pk_planes, pk[order], fmt.bits_pk)
    if plan.mode == VALUE_PLANES:
        val_planes = row[o_val:o_val + plan.bits * fmt.cap_bytes].reshape(
            plan.bits, fmt.cap_bytes)
        _pack_planes(val_planes, vidx[order], plan.bits)
    elif plan.mode == VALUE_F32:
        m = len(order)
        row[o_val:o_val + m * 4] = (np.asarray(
            value, dtype=np.float32)[order].view(np.uint8))
    elif plan.mode == VALUE_F16:
        m = len(order)
        row[o_val:o_val + m * 2] = (np.asarray(
            value, dtype=np.float32)[order].astype(
                np.float16).view(np.uint8))


def _round8(x: int) -> int:
    return max(8, (x + 7) & ~7)


def _rle_split(sorted_ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Run-length encode a sorted id column, splitting runs at _RUN_SPLIT."""
    if len(sorted_ids) == 0:
        return (np.zeros(0, dtype=np.uint32), np.zeros(0, dtype=np.uint32))
    change = np.flatnonzero(np.diff(sorted_ids)) + 1
    starts = np.concatenate([[0], change])
    ends = np.concatenate([change, [len(sorted_ids)]])
    u_out, c_out = [], []
    for s, e in zip(starts, ends):
        run = int(e - s)
        uid = sorted_ids[s]
        while run > _RUN_SPLIT:
            u_out.append(uid)
            c_out.append(_RUN_SPLIT)
            run -= _RUN_SPLIT
        u_out.append(uid)
        c_out.append(run)
    return (np.asarray(u_out, dtype=np.uint32),
            np.asarray(c_out, dtype=np.uint32))


# ---------------------------------------------------------------------------
# Device-side decode (all inside jit; fmt fields are static).
# ---------------------------------------------------------------------------


def _unpack_le(buf: jnp.ndarray, nbytes: int) -> jnp.ndarray:
    """[m, nbytes] uint8 -> int32 (little-endian)."""
    acc = buf[:, 0].astype(jnp.int32)
    for b in range(1, nbytes):
        acc = acc | (buf[:, b].astype(jnp.int32) << (8 * b))
    return acc


def _unpack_planes(planes: jnp.ndarray, bits: int, cap: int) -> jnp.ndarray:
    """[bits, cap//8] uint8 bit-planes -> int32 [cap]. Elementwise only."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    acc = jnp.zeros((cap,), dtype=jnp.int32)
    for j in range(bits):
        b = ((planes[j][:, None] >> shifts) & jnp.uint8(1)).reshape(cap)
        acc = acc | (b.astype(jnp.int32) << j)
    return acc


def decode_bucket(
    row: jnp.ndarray,
    n_valid: jnp.ndarray,
    n_uniq: jnp.ndarray,
    fmt: WireFormat,
    value_as_index: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray], jnp.ndarray]:
    """Decode one bucket row of the slab -> (pid, pk, value|None, valid).

    pid is the shifted (pid - pid_lo) id. In PID_RLE mode rows come back in
    the bucket's pid-sorted order — nondecreasing by construction (sorted
    RLE entries expanded in sequence, padding repeating the last id), which
    is the invariant the fused kernel's presorted sampler relies on. In
    PID_PLANES mode rows are in arrival order (no sortedness guarantee).
    Rows >= n_valid are garbage with valid=False.

    value_as_index (VALUE_PLANES only): return the raw int32 plane index
    instead of the reconstructed float32 — the kernel then carries the
    narrow index through its sort and widens with the identical
    ``lo + idx * scale`` float32 expression afterwards, so released
    values are bit-for-bit unchanged.
    """
    o_cnt, o_pk, o_val, _ = fmt._offsets
    cap, ucap = fmt.cap, fmt.ucap

    if fmt.pid_mode == PID_PLANES:
        pid = _unpack_planes(
            row[:o_cnt].reshape(fmt.bits_pid, fmt.cap_bytes), fmt.bits_pid,
            cap)
    else:
        uniq = _unpack_le(row[:o_cnt].reshape(ucap, fmt.bytes_pid),
                          fmt.bytes_pid)
        cnts = _unpack_le(row[o_cnt:o_pk].reshape(ucap, 2), 2)
        uvalid = jnp.arange(ucap, dtype=jnp.int32) < n_uniq
        cnts = jnp.where(uvalid, cnts, 0)
        starts = jnp.cumsum(cnts) - cnts
        # Padded entries scatter out of range and are dropped.
        starts = jnp.where(uvalid, starts, cap)
        run_of_row = jnp.cumsum(
            jnp.zeros((cap,), jnp.int32).at[starts].add(1, mode="drop")) - 1
        run_of_row = jnp.clip(run_of_row, 0, ucap - 1)
        pid = uniq[run_of_row]

    pk = _unpack_planes(
        row[o_pk:o_val].reshape(fmt.bits_pk, fmt.cap_bytes), fmt.bits_pk,
        cap)

    plan = fmt.value
    if plan.mode == VALUE_PLANES:
        idx = _unpack_planes(
            row[o_val:o_val + plan.bits * fmt.cap_bytes].reshape(
                plan.bits, fmt.cap_bytes), plan.bits, cap)
        if value_as_index:
            valid = jnp.arange(cap, dtype=jnp.int32) < n_valid
            return pid, pk, idx, valid
        # Must mirror the host verification expression exactly (f32 ops).
        value = (jnp.float32(plan.lo)
                 + idx.astype(jnp.float32) * jnp.float32(plan.scale))
    elif plan.mode == VALUE_F32:
        b = row[o_val:o_val + cap * 4].reshape(cap, 4)
        u32 = (b[:, 0].astype(jnp.uint32)
               | (b[:, 1].astype(jnp.uint32) << 8)
               | (b[:, 2].astype(jnp.uint32) << 16)
               | (b[:, 3].astype(jnp.uint32) << 24))
        value = jax.lax.bitcast_convert_type(u32, jnp.float32)
    elif plan.mode == VALUE_F16:
        b = row[o_val:o_val + cap * 2].reshape(cap, 2)
        u16 = (b[:, 0].astype(jnp.uint16)
               | (b[:, 1].astype(jnp.uint16) << 8))
        value = jax.lax.bitcast_convert_type(u16, jnp.float16).astype(
            jnp.float32)
    else:
        value = None

    valid = jnp.arange(cap, dtype=jnp.int32) < n_valid
    return pid, pk, value, valid


# ---------------------------------------------------------------------------
# Native dispatch.
# ---------------------------------------------------------------------------


def _load_packer():
    """The native row-packer library, or None (cached by the loader).

    Only loader/build failures fall back (the codec is an optimization;
    loader.LOADER_ERRORS is the typed set) — anything else, including
    NativeRequiredError under PIPELINEDP_TPU_REQUIRE_NATIVE=1, must
    propagate rather than silently downgrade to the numpy encoder (the
    `_pack_native` pattern, ops/streaming.py)."""
    from pipelinedp_tpu.native import loader
    try:
        lib = loader.load_row_packer()
    except loader.LOADER_ERRORS:
        profiler.count_event("runtime/native_fallback")
        return None
    if lib is None or not hasattr(lib, "pdp_rle_prep"):
        return None
    return lib


class NativeRleEncoder:
    """Stateful handle over the native prep/sort/emit codec.

    The split API exists for pipelining: `sort_range`+`emit_range` of slab
    s+1 runs on the host CPU while slab s's async device_put is still on
    the wire (ops/streaming.py drives this). When `entry_counts` is
    available (prep counted RLE entries exactly without sorting), the wire
    format can be fixed up front and the expensive per-bucket radix sort
    itself joins the pipeline — sort slab s+1 while slab s is in flight.
    Use as a context manager or call close(); create() returns None when
    the native library is unavailable (callers fall back to
    encode_buckets_numpy).
    """

    def __init__(self, lib, handle, counts, k, plan, entry_counts=None,
                 max_run: int = -1):
        self._lib = lib
        self._handle = handle
        self.counts = counts
        self._k = k
        self._plan = plan
        # Exact per-bucket RLE entry counts from prep (pre-sort), or None
        # when the pid span exceeded the native count-table budget.
        self.entry_counts = entry_counts
        # Max rows of any single pid (same count table; -1 = uncounted).
        self.max_run = max_run

    @property
    def plan(self) -> ValuePlan:
        """The value plan in effect (inline-vidx preps correct the bit
        width to the observed max index)."""
        return self._plan

    @classmethod
    def create(cls, pid, pk, value, vidx, *, pid_lo: int, k: int,
               plan: ValuePlan,
               inline_vidx: bool = False,
               out_status: Optional[dict] = None,
               pid_span: int = -1
               ) -> Optional["NativeRleEncoder"]:
        """inline_vidx: for PLANES plans, let the C++ prep compute AND
        bit-verify the value index during its scatter pass (vidx must be
        None). On verification failure returns None and sets
        out_status["inline_failed"] = True — callers re-plan. The
        returned encoder's plan carries the true bit width (from the
        observed max index).

        pid_span: max(pid) - pid_lo; when >= 0 and within the native
        count-table budget, prep also returns exact per-bucket RLE entry
        counts (encoder.entry_counts) without sorting."""
        lib = _load_packer()
        if lib is None:
            return None
        import ctypes

        n = len(pid)
        pid32 = np.ascontiguousarray(pid, dtype=np.int32)
        pk32 = np.ascontiguousarray(pk, dtype=np.int32)
        use_inline = inline_vidx and plan.mode == VALUE_PLANES
        val32 = (np.ascontiguousarray(value, dtype=np.float32)
                 if value is not None
                 and (use_inline or plan.mode in (VALUE_F32, VALUE_F16))
                 else None)
        vidx32 = (np.ascontiguousarray(vidx, dtype=np.int32)
                  if plan.mode == VALUE_PLANES and not use_inline else None)
        counts = np.zeros(k, dtype=np.int64)
        entries = np.zeros(k, dtype=np.int64)
        # stats: [0] inline verification failed, [1] max value index,
        # [2] max rows of any single pid (ABI 7; -1 when uncounted).
        stats = np.zeros(3, dtype=np.int64)
        handle = lib.pdp_rle_prep(
            pid32.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            pk32.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            val32.ctypes.data_as(ctypes.c_void_p) if val32 is not None
            else None,
            vidx32.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
            if vidx32 is not None else None,
            float(plan.lo), float(plan.scale),
            n, int(pid_lo), k, int(plan.mode), int(pid_span),
            entries.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            stats.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        if not handle:
            if out_status is not None and use_inline and stats[0] == 1:
                out_status["inline_failed"] = True
            return None
        if use_inline:
            plan = dataclasses.replace(
                plan, bits=max(1, int(stats[1]).bit_length()))
        entry_counts = None if entries[0] < 0 else entries
        return cls(lib, handle, counts, k, plan, entry_counts,
                   max_run=int(stats[2]))

    def sort_range(self, b0: int, b1: int) -> np.ndarray:
        """Sorts buckets [b0, b1) by pid; returns their RLE entry counts."""
        import ctypes
        n_uniq = np.zeros(b1 - b0, dtype=np.int64)
        rc = self._lib.pdp_rle_sort_range(
            self._handle, b0, b1,
            n_uniq.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        if rc != 0:
            raise RuntimeError(f"pdp_rle_sort_range failed (rc={rc})")
        return n_uniq

    def emit_range(self, b0: int, b1: int, fmt: WireFormat) -> np.ndarray:
        """Writes the flat [b1-b0, fmt.width] slab: sorted RLE rows in
        PID_RLE mode, arrival-order pid bit-planes in PID_PLANES mode."""
        import ctypes
        out = np.empty((b1 - b0, fmt.width), dtype=np.uint8)
        rc = self._lib.pdp_rle_emit_range(
            self._handle, b0, b1, int(fmt.pid_mode), fmt.bytes_pid,
            int(fmt.bits_pid), fmt.bits_pk,
            int(self._plan.bits), fmt.cap, fmt.ucap,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), fmt.width)
        if rc != 0:
            raise RuntimeError(f"pdp_rle_emit_range failed (rc={rc})")
        return out

    def close(self):
        if self._handle:
            self._lib.pdp_rle_free(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        self.close()


def encode_buckets_native(
    pid: np.ndarray,
    pk: np.ndarray,
    value: Optional[np.ndarray],
    *,
    pid_lo: int,
    k: int,
    bytes_pid: int,
    bits_pk: int,
    plan: ValuePlan,
    vidx: Optional[np.ndarray] = None,
):
    """C++ fast path (single shot over all buckets); returns the same
    tuple as encode_buckets_numpy, or None when unavailable."""
    if plan.mode == VALUE_PLANES and vidx is None:
        vidx = np.rint(
            (np.asarray(value, dtype=np.float64) - float(plan.lo))
            / float(plan.scale)).astype(np.int32)
    enc = NativeRleEncoder.create(pid, pk, value, vidx, pid_lo=pid_lo, k=k,
                                  plan=plan)
    if enc is None:
        return None
    with enc:
        n = len(pid)
        n_uniq = enc.sort_range(0, k)
        fmt = WireFormat(bytes_pid=bytes_pid, bits_pk=bits_pk,
                         cap=_round8(int(enc.counts.max()) if n else 8),
                         ucap=_round8(int(n_uniq.max()) if n else 8),
                         value=plan)
        slab = enc.emit_range(0, k, fmt)
        return slab, enc.counts, n_uniq, fmt


def encode_buckets(pid, pk, value, *, pid_lo, k, bytes_pid, bits_pk, plan,
                   vidx=None):
    """Native encoder with numpy fallback; identical outputs either way."""
    out = encode_buckets_native(pid, pk, value, pid_lo=pid_lo, k=k,
                                bytes_pid=bytes_pid, bits_pk=bits_pk,
                                plan=plan, vidx=vidx)
    if out is None:
        out = encode_buckets_numpy(pid, pk, value, pid_lo=pid_lo, k=k,
                                   bytes_pid=bytes_pid, bits_pk=bits_pk,
                                   plan=plan)
    return out


# Largest (pid_span + 1) for which the numpy fallback counts exact RLE
# entries before sorting (mirrors kMaxEntryCountSpan in row_packer.cc; the
# extra 4*n guard keeps the span pass proportional to the data).
_MAX_ENTRY_COUNT_SPAN = 1 << 26


def rle_entry_stats_numpy(pid, pid_lo: int, k: int, pid_span: int
                          ) -> Tuple[Optional[np.ndarray], int]:
    """(per-bucket RLE entry counts, max rows of any single pid) WITHOUT
    sorting, or (None, -1) when the pid span is too large to count
    cheaply.

    A pid hashes to exactly one bucket, so bucket b's post-sort entry
    count is sum(ceil(rows_of_pid / 65535)) over the pids landing in b —
    computable from a per-pid bincount. This is what lets the caller fix
    the wire format before any sort and pipeline the sort per slab. The
    max per-pid row count from the same bincount bounds every pid-segment
    span in every bucket — the tile-slack input of the segment-local
    tiled sort (plan_segment_tiling).
    """
    n = len(pid)
    if pid_span < 0 or pid_span + 1 > min(_MAX_ENTRY_COUNT_SPAN,
                                          max(4 * n, 1 << 22)):
        return None, -1
    shifted = (np.asarray(pid) - pid_lo).astype(np.int64, copy=False)
    per = np.bincount(shifted, minlength=pid_span + 1)
    nz = np.flatnonzero(per)
    bucket = ((nz.astype(np.uint32) * _HASH_MULT) >> np.uint32(16)) % \
        np.uint32(k)
    entries = -(-per[nz] // _RUN_SPLIT)
    counts = np.bincount(bucket, weights=entries,
                         minlength=k).astype(np.int64)
    return counts, int(per.max()) if n else 0


def rle_entry_counts_numpy(pid, pid_lo: int, k: int,
                           pid_span: int) -> Optional[np.ndarray]:
    """rle_entry_stats_numpy without the max-run stat (compat surface)."""
    return rle_entry_stats_numpy(pid, pid_lo, k, pid_span)[0]


def plan_segment_tiling(fmt: WireFormat, segment_sort,
                        max_run: int) -> WireFormat:
    """Resolves the ``segment_sort`` knob into tile geometry on ``fmt``.

    segment_sort: False disables; True forces tiling whenever the
    geometry is non-degenerate; "auto" additionally requires enough tiles
    per bucket (>= 8) that the shorter sort span pays for the binning and
    compaction passes. Tiling needs the max single-pid run (``max_run``,
    from prep-time per-pid counts — tile_slack must bound every segment;
    unknown/-1 disables) and pid-sorted arrival (PID_RLE).

    Tile width: the smallest power of two >= 4 * max_run (so slack stays
    <= ~25% of a tile) and >= 1024 (smaller tiles are all padding).
    """
    if segment_sort is False or fmt.pid_mode != PID_RLE:
        return fmt
    if max_run is None or max_run <= 0:
        return fmt
    slack = _round8(max_run)
    tile = 1 << max(10, (4 * max_run - 1).bit_length())
    if tile + slack >= fmt.cap:
        return fmt
    if segment_sort == "auto" and tile > fmt.cap // 8:
        return fmt
    return dataclasses.replace(fmt, tile_rows=tile, tile_slack=slack)


# Hash-binned group-stage geometry limits (plan_group_binning). The bin
# width bounds the O(W^2) pairwise selection per segment — beyond
# HASH_MAX_BIN_ROWS the quadratic term loses to the tiled sort, so auto
# declines (forced "hash" tolerates up to HASH_FORCED_MAX_BIN_ROWS, the
# compile-sanity ceiling). HASH_GRID_BLOWUP bounds the [bins, width]
# grid relative to the chunk's rows: bins beyond the budget are not
# allocated — chunks needing them demote to the tiled kernel per chunk.
HASH_MAX_BIN_ROWS = 128
HASH_FORCED_MAX_BIN_ROWS = 1024
HASH_GRID_BLOWUP = 4


def plan_group_binning(fmt: WireFormat, segment_sort, max_run: int, *,
                       exact: bool = False) -> WireFormat:
    """plan_segment_tiling extended to the 4-way sampler plan: resolves
    the ``segment_sort`` knob into tile geometry AND, for the sortless
    group stage, the ``[hash_bins, hash_bin_rows]`` bin grid.

    segment_sort="hash" forces the hash-binned stage whenever its
    geometry is computable (pid-sorted wire, known max_run, bin width
    within the forced ceiling); "auto" additionally requires ``exact``
    (the caller-evaluated columnar.hash_exact_gate — bit-identity to
    the sorted paths), the auto bin-width ceiling, and bins for every
    chunk within the grid budget (so auto never mixes kernels). The
    tile geometry is always resolved too: it is the per-chunk demotion
    target when a chunk's RLE entry count exceeds hash_bins.

    Bin sizing from the row_packer prep stats: width = the max
    single-pid run rounded up (a segment can never overflow its bin —
    only corrupt wire metadata can, and the kernel backstop empties the
    accumulators then), bins = the per-bucket RLE entry capacity
    (every segment gets a bin) capped by the grid byte budget.
    """
    fmt = plan_segment_tiling(fmt, segment_sort, max_run)
    if segment_sort is False or fmt.pid_mode != PID_RLE:
        return fmt
    if max_run is None or max_run <= 0:
        return fmt
    forced = segment_sort == "hash"
    if not forced and not (segment_sort == "auto" and exact):
        return fmt
    w = _round8(max_run)
    if w > (HASH_FORCED_MAX_BIN_ROWS if forced else HASH_MAX_BIN_ROWS):
        return fmt
    budget = max(8, (HASH_GRID_BLOWUP * fmt.cap) // w)
    bins = min(_round8(fmt.ucap), _round8(budget))
    if bins < 8:
        return fmt
    if not forced and bins < fmt.ucap:
        # auto never plans a grid some chunks would overflow (mixed
        # hash/tiled execution is the forced knob's explicit trade).
        return fmt
    return dataclasses.replace(fmt, hash_bins=int(bins),
                               hash_bin_rows=int(w))


def choose_pid_mode(n: int, pid_span: int, bytes_pid: int,
                    entry_counts: Optional[np.ndarray]) -> Tuple[int, int]:
    """(pid_mode, bits_pid) for this dataset.

    PID_PLANES wins when the arrival-order bit-planes are strictly smaller
    on the wire than the RLE entries — near-unique privacy ids — since it
    also skips the host radix sort entirely (the device sorts anyway).
    With repetitive ids (the headline movie-ratings shape: ~10 rows/user,
    RLE ~0.3 bits/row vs 24 plane bits) RLE stays, and it additionally
    hands the kernel the pid-sorted arrival order (presorted sampler).
    Unknown entry counts (huge span) keep RLE with the upfront sort.
    """
    bits_pid = max(1, int(pid_span).bit_length())
    if entry_counts is None:
        return PID_RLE, bits_pid
    plane_bits = n * bits_pid
    rle_bits = int(entry_counts.sum()) * (8 * bytes_pid + 16)
    return (PID_PLANES if plane_bits < rle_bits else PID_RLE), bits_pid


def _sample_plan(value: Optional[np.ndarray],
                 value_f16: bool) -> ValuePlan:
    """Tentative plan from the 64k-sample gate only (one cheap pass plus
    the global min). A PLANES result is provisional: the native prep
    verifies the full array bit-exactly during its scatter pass. Shares
    the scale ladder and gate with plan_and_index."""
    final, value, lo, lo64, sample = _plan_preamble(value, value_f16)
    if final is not None:
        return final
    for scale, s in _gated_scales(sample, lo, lo64):
        return ValuePlan(VALUE_PLANES, bits=1, lo=float(lo),
                         scale=float(s))
    return ValuePlan(VALUE_F32)


@dataclasses.dataclass(frozen=True)
class EncodeInfo:
    """Everything the streaming drivers need to build wire formats and
    schedule the encode pipeline (make_encoder's planning output)."""
    plan: ValuePlan
    vidx: Optional[np.ndarray]  # value index (numpy fallback PLANES only)
    pid_lo: int
    pid_span: int
    bytes_pid: int
    bits_pk: int
    pid_mode: int  # PID_RLE or PID_PLANES
    bits_pid: int  # pid plane count (PID_PLANES)
    # Exact per-bucket RLE entry counts known BEFORE sorting, or None
    # (then PID_RLE callers must learn ucap from an upfront sort).
    entry_counts: Optional[np.ndarray]
    # Max rows of any single pid (bounds every pid segment in every
    # bucket — the tile-slack input of plan_segment_tiling), or -1 when
    # the span was too large to count.
    max_run: int = -1


def make_encoder(pid: np.ndarray, pk, value, *, num_partitions: int, k: int,
                 value_transfer_dtype=None
                 ) -> Tuple[Optional[NativeRleEncoder], EncodeInfo]:
    """Shared encode prologue of the single-device and mesh streaming
    paths: pid-span validation, width/bit planning, value plan + index,
    the pid wire-mode decision, and the native encoder (None -> numpy
    fallback).

    With the native library, the full-array value verification happens
    INSIDE the C++ scatter pass (no separate host pass); without it, the
    chunked host verification of plan_and_index runs for the numpy
    fallback.

    Each pass is a child span of the callers' ``dp/wire_prep``:
    ``dp/wire_prep/pid_range`` (pid min/max), ``.../value_plan`` (the
    sample plan, or the full plan on the re-plan and numpy paths),
    ``.../native_scan`` (the native scatter / RLE-count pass) and
    ``.../fallback_stats`` (numpy RLE entry counts).

    Returns (enc_or_None, EncodeInfo).
    """
    pid = np.asarray(pid)
    with obs_trace.span("dp/wire_prep/pid_range"):
        pid_lo = int(pid.min())
        pid_span = int(pid.max()) - pid_lo
    if pid_span >= np.iinfo(np.int32).max - 1:
        # The kernel reserves INT32_MAX as its padding sentinel; a shifted
        # pid colliding with it would be silently dropped.
        raise ValueError(
            f"privacy-id span {pid_span} does not fit int32; factorize the "
            f"ids to dense int32 before streaming")
    bytes_pid = 1
    while pid_span >= (1 << (8 * bytes_pid)):
        bytes_pid += 1
    bits_pk = max(1, int(max(num_partitions - 1, 0)).bit_length())
    value_f16 = (value_transfer_dtype is not None
                 and np.dtype(value_transfer_dtype) == np.float16)

    def info_for(plan, vidx, entry_counts, max_run=-1):
        pid_mode, bits_pid = choose_pid_mode(len(pid), pid_span, bytes_pid,
                                             entry_counts)
        return EncodeInfo(plan=plan, vidx=vidx, pid_lo=pid_lo,
                          pid_span=pid_span, bytes_pid=bytes_pid,
                          bits_pk=bits_pk, pid_mode=pid_mode,
                          bits_pid=bits_pid, entry_counts=entry_counts,
                          max_run=max_run)

    def full_plan():
        with obs_trace.span("dp/wire_prep/value_plan", full=True):
            return plan_and_index(value, value_f16)

    def numpy_stats():
        with obs_trace.span("dp/wire_prep/fallback_stats"):
            return rle_entry_stats_numpy(pid, pid_lo, k, pid_span)

    def native_scan(plan, vidx, **kw):
        with obs_trace.span("dp/wire_prep/native_scan"):
            return NativeRleEncoder.create(pid, pk, value, vidx,
                                           pid_lo=pid_lo, k=k, plan=plan,
                                           pid_span=pid_span, **kw)

    def fallback_info():
        plan, vidx = full_plan()
        entries, max_run = numpy_stats()
        return info_for(plan, vidx, entries, max_run)

    if _load_packer() is None:
        # Numpy fallback: needs the fully verified plan and index on the
        # host (and must not pay the sample pass twice).
        return None, fallback_info()

    with obs_trace.span("dp/wire_prep/value_plan", full=False):
        tentative = _sample_plan(value, value_f16)
    status: dict = {}
    enc = native_scan(tentative, None, inline_vidx=True, out_status=status)
    if enc is not None:
        return enc, info_for(enc.plan, None, enc.entry_counts, enc.max_run)
    if status.get("inline_failed"):
        # The sample-chosen scale failed the full array: re-plan with the
        # full chunked host verification (which tries the other scales)
        # and retry — rare, and only costs the fallback pass.
        plan, vidx = full_plan()
        enc = native_scan(plan, vidx)
        if enc is not None:
            return enc, info_for(plan, vidx, enc.entry_counts, enc.max_run)
        entries, max_run = numpy_stats()
        return None, info_for(plan, vidx, entries, max_run)
    return None, fallback_info()


def resident_fingerprint(k: int, fmt: WireFormat, counts: np.ndarray,
                         n_uniq: Optional[np.ndarray],
                         data_digest: str = "") -> str:
    """Identity of a retained wire handle (streaming.ResidentWire).

    Reuses the checkpoint wire-fingerprint path — chunk count, format,
    per-bucket row/entry counts, plus the source-column digest
    (runtime.checkpoint.array_digest) — so a resident-dataset session
    names its handle exactly the way a resumed slab loop names its wire,
    and a source dataset mutated after ingest is refused on the same
    evidence a mutated checkpoint input is.
    """
    from pipelinedp_tpu.runtime import checkpoint as checkpoint_lib

    return checkpoint_lib.wire_fingerprint(k, repr(fmt), counts, n_uniq,
                                           data_digest=data_digest)


def round_ucap(umax: int) -> int:
    """Rounds an RLE entry count up with ~12.5% granularity so slab shapes
    recur across slabs/runs (each distinct shape is a fresh XLA compile)."""
    umax = max(umax, 8)
    g = max(8, 1 << max(3, umax.bit_length() - 3))
    return -(-umax // g) * g
