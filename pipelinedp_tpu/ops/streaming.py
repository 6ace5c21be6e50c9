"""Streaming (chunked) execution of the fused DP-aggregation kernel.

The columnar engine's end-to-end cost on real hardware is dominated by the
host->device transfer of the row columns, not by the kernel (BASELINE.md
headline workload: ~1.2 GB of columns vs a ~15 s fused kernel). This module
turns the single-shot `columnar.bound_and_aggregate` call into a pipeline of
pid-disjoint chunks so that

  * the transfer of chunk k+1 overlaps the kernel of chunk k (the dispatch
    queue is async end to end),
  * each chunk ships byte-packed to the minimal width its id ranges need
    (privacy ids and partition ids rarely need 4 bytes each), and
  * the `valid` mask is never transferred at all (it is `iota < n` on
    device).

Chunks are made pid-disjoint by hash-sharding rows on the privacy id, which
is what makes the result exact rather than approximate: contribution
bounding (the Linf/L0 sampling of `ops/columnar.py`) only looks at rows of
one privacy id at a time, so bounding each shard independently with the full
caps and summing the per-partition accumulators is *identical in
distribution* to bounding the whole dataset at once (same role as the
per-key sampling of the reference, contribution_bounders.py:62-111 — the
key-space split is just a different iteration order). Privacy-id counts add
across shards because a pid lives in exactly one shard.

The same trick is used across devices by `parallel/sharded.py`; here it is
used across *time* on one device.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pipelinedp_tpu.ops import columnar, wirecodec
from pipelinedp_tpu import profiler
from pipelinedp_tpu.obs import trace as obs_trace
from pipelinedp_tpu.runtime import driver as driver_lib

# Knuth multiplicative hash so that structured pid spaces (all-even ids,
# contiguous ranges handed out per site, ...) still shard evenly.
_HASH_MULT = np.uint32(2654435761)

# Row count below which the single-shot path wins (chunking only adds
# dispatch latency when the transfer is small).
MIN_STREAM_ROWS = 2_000_000

# Each chunk re-scatters into the full [num_partitions] accumulators, so
# chunk count multiplies the per-partition segment-sum cost while overlap
# only needs a few slabs in flight. Fewer chunks mean a larger per-chunk
# program shape and a longer compile. The balance point on a PCIe-attached
# chip is not measured yet; 8 is the value the engine has always used.
DEFAULT_NUM_CHUNKS = 8

# Transfers are sized by a byte budget, not a fixed count: small inputs take
# 2 slabs (the minimum that overlaps transfer with compute), huge inputs
# take as many as keep a slab near the budget so peak device residency per
# slab stays bounded.
SLAB_BYTE_BUDGET = 192 * 1024 * 1024

# When the host radix sort rides the slab pipeline (exact RLE entry counts
# known at prep time), finer slabs buy overlap: each slab's sort runs while
# the previous slab's transfer + kernels are in flight, so more slabs hide
# more of the single-core sort. Still bounded below (2) and by the bucket
# count; per-transfer fixed costs keep this from going per-row.
PIPELINED_SLAB_BYTE_BUDGET = 48 * 1024 * 1024

# Tuning knobs (validated in loader.env_int; README "Tuning knobs").
# PIPELINEDP_TPU_SLAB_BYTES overrides BOTH slab byte budgets above;
# PIPELINEDP_TPU_PREFETCH_SLABS bounds the background encode lookahead
# (0 disables prefetch, default 1 slab ahead).
SLAB_BYTES_ENV = "PIPELINEDP_TPU_SLAB_BYTES"
PREFETCH_ENV = "PIPELINEDP_TPU_PREFETCH_SLABS"

# Profiler event counters (profiler.count_event / event_count), counted
# per EXECUTED pass by the unified slab driver (runtime/driver.py, where
# the per-chunk counters are canonical):
#   EVENT_PARTITION_SCATTERS — full-[num_partitions] scatter passes whose
#     input is row/group scale (the expensive kind: one per accumulator
#     per chunk on the legacy path);
#   EVENT_COMPACT_MERGE_SCATTERS — [num_partitions] scatters whose input
#     is the compact per-chunk subtotal columns (once per accumulator per
#     MERGE, not per chunk; counted by the merge closures here);
#   EVENT_COMPACT_CHUNKS — chunks that emitted compact group columns.
EVENT_PARTITION_SCATTERS = driver_lib.EVENT_PARTITION_SCATTERS
EVENT_COMPACT_MERGE_SCATTERS = "ops/compact_merge_scatter_passes"
EVENT_COMPACT_CHUNKS = driver_lib.EVENT_COMPACT_CHUNKS

# compact_merge="auto" engages the compact chunk merge at this partition
# count and above. The merge trades the per-chunk full-[num_partitions]
# scatter passes for a per-chunk compaction (group stage + a [G]-sized
# sort) — a win exactly when the [P]-output passes dominate (the 1M-
# partition headline regime: BASELINE.md round-4 measured ~0.74 s per
# full-partition pass on the bench chip), a loss when P is small and the
# partition passes are nearly free (the CPU smoke at 30k partitions
# measured the compaction overhead at ~2x the whole legacy kernel).
COMPACT_MIN_PARTITIONS = 1 << 17


def finish_wire_plan(fmt, segment_sort, max_run, *, num_partitions: int,
                     row_clip_lo, row_clip_hi, linf_cap, l1_mode: bool,
                     group_clip_lo=-np.inf, group_clip_hi=np.inf,
                     need_flags=(True, True, True, True)):
    """Finalizes a wire format for the chunk kernels -> (fmt, int_clip).
    Shared by the single-device slab loop and the mesh chunk loop
    (parallel/sharded.py) so both paths resolve the segment_sort knob
    and the int32-accumulation gate identically.

    fmt gains tile geometry and (segment_sort "hash", or "auto" under
    the order-exactness gate) the hash-bin grid of the sortless group
    stage (wirecodec.plan_group_binning — the 4-way
    general/packed/tiled/hash dispatch); int_clip is the int32 row-clip
    pair when VALUE_PLANES chunks may accumulate in int32 bit-identically
    (columnar.int_accumulation_plan), else None.

    "auto" picks the hash-binned stage only when it is provably
    bit-identical to the sorted paths: columnar.hash_exact_gate holds
    (every float32 partial sum is an exact integer, so the different
    accumulation order cannot change a bit), the kernel reads no norm
    columns (mean/variance sums are non-integer), no L1 mode, and the
    grid fits every chunk. segment_sort="hash" forces the stage whenever
    its geometry is computable — exact counts, ULP-close sums outside
    the gate, with the tiled path as the parity oracle.

    segment_sort=False is the full round-8 parity oracle: no tiling, the
    value widens to float32 at decode (f32 sort payload), and the group
    stage accumulates in float32 — so the knob A/Bs this PR's whole
    kernel-side change, not just the tile geometry."""
    if segment_sort is False:
        fmt = dataclasses.replace(fmt, tile_rows=0, tile_slack=0,
                                  hash_bins=0, hash_bin_rows=0,
                                  sort_value_narrow=False)
        clip = None
    else:
        clip = None
        exact = False
        if fmt.value.mode == wirecodec.VALUE_PLANES:
            clip = columnar.int_accumulation_plan(
                fmt.value.lo, fmt.value.scale, fmt.value.bits,
                row_clip_lo, row_clip_hi, linf_cap)
            if (clip is not None and not l1_mode
                    and not (need_flags[2] or need_flags[3])):
                exact = columnar.hash_exact_gate(
                    fmt.value.lo, fmt.value.scale, fmt.value.bits,
                    row_clip_lo, row_clip_hi, linf_cap,
                    group_clip_lo, group_clip_hi, fmt.cap)
        fmt = wirecodec.plan_group_binning(fmt, segment_sort, max_run,
                                           exact=exact)
        if clip is not None:
            clip = (np.int32(clip[0]), np.int32(clip[1]))
    return fmt, clip


def _sampler_geometry(fmt) -> dict:
    """The tile and hash statics a wire chunk kernel hands its sampler:
    the format's geometry on a pid-sorted wire, none otherwise."""
    on = fmt.pid_sorted
    return dict(tile_rows=fmt.tile_rows if on else 0,
                tile_slack=fmt.tile_slack if on else 0,
                hash_bins=fmt.hash_bins if on else 0,
                hash_bin_rows=fmt.hash_bin_rows if on else 0)


def wire_sampler_kind(fmt, *, num_partitions: int, l1_mode: bool) -> str:
    """columnar.sampler_kind of the chunk kernels compiled against a
    finished wire format, from the statics they pass."""
    return columnar.sampler_kind(
        fmt.cap, num_partitions=num_partitions,
        max_segments=fmt.ucap if fmt.pid_sorted else None,
        pid_sorted=fmt.pid_sorted, l1_mode=l1_mode,
        **_sampler_geometry(fmt))


def resolved_sampler_desc(fmt, segment_sort, max_run, *,
                          num_partitions: int, row_clip_lo, row_clip_hi,
                          linf_cap, l1_mode: bool, group_clip_lo,
                          group_clip_hi, need_flags) -> str:
    """Opaque identity of the RESOLVED sampler a query config runs —
    the sampler kind plus the finished wire-format geometry (tile/hash
    fields, narrow-payload flag) the chunk kernels compile against.

    Two knob settings that resolve to the same kernel get the same
    descriptor; the same knob string resolving differently (e.g. "auto"
    picking hash under the exactness gate vs tiled outside it) gets a
    different one. The serving bound cache keys on this instead of the
    raw knob string, so flipping ``segment_sort`` between queries can
    never alias a cached accumulator across samplers (the checkpoint
    path gets the same guarantee from ``repr(fmt)`` riding the wire
    fingerprint).
    """
    fmt2, _ = finish_wire_plan(
        fmt, segment_sort, max_run, num_partitions=num_partitions,
        row_clip_lo=row_clip_lo, row_clip_hi=row_clip_hi,
        linf_cap=linf_cap, l1_mode=l1_mode, group_clip_lo=group_clip_lo,
        group_clip_hi=group_clip_hi, need_flags=tuple(need_flags))
    kind = wire_sampler_kind(fmt2, num_partitions=num_partitions,
                             l1_mode=l1_mode)
    return f"{kind}:{fmt2!r}"


def _compact_enabled(compact_merge, num_partitions: int) -> bool:
    """Resolves the compact_merge knob (True / False / "auto")."""
    if compact_merge is True:
        return True
    if compact_merge == "auto":
        return num_partitions >= COMPACT_MIN_PARTITIONS
    return False


def prefetch_depth() -> int:
    """Validated PIPELINEDP_TPU_PREFETCH_SLABS (0..4, default 1): how many
    slab windows the background encoder may run ahead of the transfer."""
    from pipelinedp_tpu.native import loader
    return loader.env_int(PREFETCH_ENV, 1, 0, 4)


def slab_byte_budget(pipelined: bool) -> int:
    """The slab byte budget, honoring the PIPELINEDP_TPU_SLAB_BYTES
    override (1 MiB .. 4 GiB)."""
    from pipelinedp_tpu.native import loader
    default = PIPELINED_SLAB_BYTE_BUDGET if pipelined else SLAB_BYTE_BUDGET
    return loader.env_int(SLAB_BYTES_ENV, default, 1 << 20, 1 << 32)


def _num_chunks(n_rows: int) -> int:
    # ~8 MB of packed bytes per chunk minimum, capped at the default.
    return int(min(DEFAULT_NUM_CHUNKS, max(2, n_rows // 1_000_000)))


def _num_transfers(total_bytes: int, k: int,
                   budget: int = SLAB_BYTE_BUDGET) -> int:
    want = -(-total_bytes // budget)  # ceil
    return int(max(2, min(k, want)))


# Encoding choice: "auto" is the lossless wire codec, which ships ~3x
# fewer bytes than the legacy fixed-width packing at the headline shape
# and decodes with contiguous bit-plane shifts instead of a strided byte
# unpack. Its end-to-end gain on a PCIe-attached chip is not measured yet.
# "bytes" stays available explicitly.


def _int_bytes(max_value: int) -> int:
    """Bytes needed to carry values in [0, max_value]."""
    for nbytes in (1, 2, 3, 4):
        if max_value < (1 << (8 * nbytes)):
            return nbytes
    raise ValueError(f"{max_value} does not fit in 4 bytes")


def _pack_ints(out: np.ndarray, col: np.ndarray, offset: int,
               nbytes: int) -> None:
    """Little-endian byte-split of an int column into out[:, offset:...]."""
    col = col.astype(np.uint32, copy=False)
    for b in range(nbytes):
        out[:, offset + b] = (col >> (8 * b)).astype(np.uint8)


def _unpack_ints(buf: jnp.ndarray, offset: int, nbytes: int) -> jnp.ndarray:
    """Device-side inverse of _pack_ints -> int32."""
    acc = buf[:, offset].astype(jnp.int32)
    for b in range(1, nbytes):
        acc = acc | (buf[:, offset + b].astype(jnp.int32) << (8 * b))
    return acc


def _unpack_value(buf: jnp.ndarray, offset: int,
                  is_f16: bool) -> jnp.ndarray:
    if is_f16:
        u16 = (buf[:, offset].astype(jnp.uint16) |
               (buf[:, offset + 1].astype(jnp.uint16) << 8))
        return jax.lax.bitcast_convert_type(u16, jnp.float16).astype(
            jnp.float32)
    u32 = (buf[:, offset].astype(jnp.uint32) |
           (buf[:, offset + 1].astype(jnp.uint32) << 8) |
           (buf[:, offset + 2].astype(jnp.uint32) << 16) |
           (buf[:, offset + 3].astype(jnp.uint32) << 24))
    return jax.lax.bitcast_convert_type(u32, jnp.float32)


def _fold_chunk(accs, chunk_accs):
    """``accs + chunk_accs`` with the chunk's partials materialized first.

    Without the barrier XLA rewrites ``accs + scatter(zeros, groups)``
    into a scatter of the groups straight onto ``accs``, which adds each
    group to the running total one by one. That is a different float
    order from the compact merge, which adds one subtotal per partition
    and chunk (columnar.merge_compact_chunks); the barrier keeps the two
    paths bit-identical (tests/compact_merge_test.py TestBitParity)."""
    chunk_accs = jax.lax.optimization_barrier(chunk_accs)
    return columnar.PartitionAccumulators(
        *(a + c for a, c in zip(accs, chunk_accs)))


@functools.partial(
    jax.jit,
    static_argnames=("num_partitions", "bytes_pid", "bytes_pk", "value_f16",
                     "need_flags", "has_group_clip"),
    donate_argnums=(3,))
def _chunk_step(key, buf, n_valid, accs, linf_cap, l0_cap, row_clip_lo,
                row_clip_hi, middle, group_clip_lo, group_clip_hi,
                l1_cap=None, *,
                num_partitions: int, bytes_pid: int, bytes_pk: int,
                value_f16: bool, need_flags=(True, True, True, True),
                has_group_clip: bool = True):
    """Unpack one byte-packed chunk, bound+aggregate it, add into accs.

    Chunks are pid-disjoint, so the optional L1 (max_contributions) sample
    inside the kernel is exact per chunk.
    """
    with jax.named_scope("decode"):
        pid = _unpack_ints(buf, 0, bytes_pid)
        pk = _unpack_ints(buf, bytes_pid, bytes_pk)
        value = _unpack_value(buf, bytes_pid + bytes_pk, value_f16)
        valid = jnp.arange(buf.shape[0], dtype=jnp.int32) < n_valid
    chunk_accs = columnar.bound_and_aggregate(
        key, pid, pk, value, valid,
        num_partitions=num_partitions,
        linf_cap=linf_cap,
        l0_cap=l0_cap,
        row_clip_lo=row_clip_lo,
        row_clip_hi=row_clip_hi,
        middle=middle,
        group_clip_lo=group_clip_lo,
        group_clip_hi=group_clip_hi,
        l1_cap=l1_cap,
        need_count=need_flags[0],
        need_sum=need_flags[1],
        need_norm=need_flags[2],
        need_norm_sq=need_flags[3],
        has_group_clip=has_group_clip)
    return _fold_chunk(accs, chunk_accs)


def _decode_for_kernel(row, n_valid, n_uniq, fmt):
    """Shared decode of the wire chunk steps: VALUE_PLANES chunks keep the
    narrow int32 plane index through the kernel's sort (widened after it
    with the identical reconstruction expression — bit-for-bit the same
    released values); other modes decode to float32 as before. Returns
    (pid, pk, value, valid, value_kwargs-for-the-kernel).

    The chunk programs name their phases with ``jax.named_scope`` —
    ``decode`` here, then ``group_sort`` (``group_sort/sample``),
    ``group_reduce``, ``compact`` and ``scatter`` in ops/columnar.py — so
    a device trace splits a chunk program's time by phase; the jit names
    stay what the benchmark's readers match."""
    value_as_index = (fmt.value.mode == wirecodec.VALUE_PLANES
                      and fmt.sort_value_narrow)
    with jax.named_scope("decode"):
        pid, pk, value, valid = wirecodec.decode_bucket(
            row, n_valid, n_uniq, fmt, value_as_index=value_as_index)
        if value is None:
            value = jnp.zeros((fmt.cap,), dtype=jnp.float32)
            value_as_index = False
    kwargs = dict(
        _sampler_geometry(fmt),
        value_is_index=value_as_index,
        value_lo=np.float32(fmt.value.lo),
        value_scale=np.float32(fmt.value.scale),
        value_sort_bits=fmt.value.bits if value_as_index else 0)
    return pid, pk, value, valid, kwargs


@functools.partial(
    jax.jit,
    static_argnames=("num_partitions", "fmt", "need_flags",
                     "has_group_clip", "int_accumulate"),
    donate_argnums=(4,))
def _chunk_step_rle(key, row, n_valid, n_uniq, accs, linf_cap, l0_cap,
                    row_clip_lo, row_clip_hi, middle, group_clip_lo,
                    group_clip_hi, l1_cap=None, int_clip=None, *,
                    num_partitions: int, fmt: wirecodec.WireFormat,
                    need_flags=(True, True, True, True),
                    has_group_clip: bool = True,
                    int_accumulate: bool = False):
    """Decode one wire-codec bucket, bound+aggregate it, add into accs.

    Buckets are pid-disjoint, so bounding each independently with the full
    caps and summing accumulators is exact (see module docstring). In
    PID_RLE mode the decoded rows are pid-sorted by construction, so the
    kernel runs its cheaper presorted sampler — tiled into bounded-span
    segment-local sorts when fmt carries tile geometry (fmt.pid_sorted
    plumbs the invariant; fmt.ucap bounds the distinct pids per bucket).
    """
    pid, pk, value, valid, vkw = _decode_for_kernel(row, n_valid, n_uniq,
                                                    fmt)
    chunk_accs = columnar.bound_and_aggregate(
        key, pid, pk, value, valid,
        num_partitions=num_partitions,
        linf_cap=linf_cap,
        l0_cap=l0_cap,
        row_clip_lo=row_clip_lo,
        row_clip_hi=row_clip_hi,
        middle=middle,
        group_clip_lo=group_clip_lo,
        group_clip_hi=group_clip_hi,
        l1_cap=l1_cap,
        need_count=need_flags[0],
        need_sum=need_flags[1],
        need_norm=need_flags[2],
        need_norm_sq=need_flags[3],
        has_group_clip=has_group_clip,
        pid_sorted=fmt.pid_sorted,
        max_segments=fmt.ucap if fmt.pid_sorted else None,
        int_accumulate=int_accumulate,
        int_clip_lo=int_clip[0] if int_clip is not None else None,
        int_clip_hi=int_clip[1] if int_clip is not None else None,
        **vkw)
    return _fold_chunk(accs, chunk_accs)


@functools.partial(
    jax.jit,
    static_argnames=("num_partitions", "fmt", "max_groups", "need_flags",
                     "has_group_clip", "int_accumulate"))
def _chunk_step_rle_compact(key, row, n_valid, n_uniq, linf_cap, l0_cap,
                            row_clip_lo, row_clip_hi, middle, group_clip_lo,
                            group_clip_hi, l1_cap=None, int_clip=None, *,
                            num_partitions: int, fmt: wirecodec.WireFormat,
                            max_groups: int,
                            need_flags=(True, True, True, True),
                            has_group_clip: bool = True,
                            int_accumulate: bool = False):
    """_chunk_step_rle that emits compact per-group columns instead of
    scattering into the full [num_partitions] accumulators.

    Same decode, same sampler (identical statics and key), same group
    accumulators — but the chunk's contribution leaves the kernel as at
    most ``max_groups`` (pk, subtotal) pairs per accumulator
    (columnar.CompactGroups); ONE final merge scatters every chunk
    (columnar.merge_compact_chunks). Nothing is donated, so a failed
    dispatch can never poison the running state.
    """
    pid, pk, value, valid, vkw = _decode_for_kernel(row, n_valid, n_uniq,
                                                    fmt)
    return columnar.bound_and_aggregate_compact(
        key, pid, pk, value, valid,
        num_partitions=num_partitions,
        max_groups=max_groups,
        linf_cap=linf_cap,
        l0_cap=l0_cap,
        row_clip_lo=row_clip_lo,
        row_clip_hi=row_clip_hi,
        middle=middle,
        group_clip_lo=group_clip_lo,
        group_clip_hi=group_clip_hi,
        l1_cap=l1_cap,
        need_count=need_flags[0],
        need_sum=need_flags[1],
        need_norm=need_flags[2],
        need_norm_sq=need_flags[3],
        has_group_clip=has_group_clip,
        pid_sorted=fmt.pid_sorted,
        max_segments=fmt.ucap if fmt.pid_sorted else None,
        int_accumulate=int_accumulate,
        int_clip_lo=int_clip[0] if int_clip is not None else None,
        int_clip_hi=int_clip[1] if int_clip is not None else None,
        **vkw)


def _merge_pending(accs, pending, num_partitions, need_flags):
    """Folds a list of CompactGroups into the dense accumulators with one
    scatter per accumulator column; validates the static group bound."""
    max_kept = int(jax.device_get(
        jnp.max(jnp.stack([p.n_kept for p in pending]))))
    max_groups = pending[0].pk.shape[0]
    if max_kept > max_groups:
        raise RuntimeError(
            f"compact merge: a chunk kept {max_kept} groups, above the "
            f"static bound {max_groups} — the pid-sorted wire contract "
            f"was violated; refusing to release truncated accumulators")
    profiler.count_event(EVENT_COMPACT_MERGE_SCATTERS,
                         1 + sum(bool(f) for f in need_flags))
    stacked = [jnp.stack([p[i] for p in pending]) for i in range(6)]
    return columnar.merge_compact_chunks(
        accs, *stacked, num_partitions=num_partitions,
        need_flags=tuple(need_flags))


def _build_chunk_steps(key, fmt, int_clip, *, num_partitions, linf_cap,
                       l0_cap, row_clip_lo, row_clip_hi, middle,
                       group_clip_lo, group_clip_hi, l1_cap, need_flags,
                       has_group_clip, quantile_spec, compact_merge):
    """(step_chunk, compact_step, merge_fn) for one finished wire format.

    The single place the per-chunk kernel closures are built, shared by
    the cold streaming path (stream_bound_and_aggregate) and the
    resident-wire replay path (replay_resident_wire), so both fold the
    identical kernels under the identical ``fold_in(key, c)`` schedule —
    the warm-path bit-parity contract of SERVING.md rests on this.

    When fmt plans the hash-binned group stage, the per-chunk demotion
    lives here: a chunk whose RLE entry count exceeds the static bin
    count runs the tiled kernel instead (a second compile of the same
    step with the hash fields zeroed) — decided on HOST data that is
    part of the wire fingerprint, so cold runs, warm replays and
    resumes demote identically and released bits never depend on it.

    Each launch counts ``ops/sampler_chunks/<kind>`` of the kernel it
    runs (the demoted kind for demoted chunks) and, on the hash stage,
    its grid occupancy.

    compact_step/merge_fn are None when the compact merge does not apply
    (knob off, too few partitions, PID_PLANES wire — no per-chunk pid
    bound — or quantile histograms, which stay on the legacy fold).
    """
    hash_on = fmt.hash_bins > 0 and fmt.pid_sorted
    fmt_demoted = (dataclasses.replace(fmt, hash_bins=0, hash_bin_rows=0)
                   if hash_on else fmt)
    l1_mode = l1_cap is not None
    kind = wire_sampler_kind(fmt, num_partitions=num_partitions,
                             l1_mode=l1_mode)
    kind_demoted = wire_sampler_kind(fmt_demoted,
                                     num_partitions=num_partitions,
                                     l1_mode=l1_mode)
    cells = max(fmt.hash_bins * fmt.hash_bin_rows, 1)

    def chunk_plan(n_uniq_c, n_valid):
        if hash_on and n_uniq_c > fmt.hash_bins:
            profiler.count_event(columnar.EVENT_HASH_DEMOTIONS)
            profiler.count_event(columnar.sampler_event(kind_demoted))
            return fmt_demoted
        profiler.count_event(columnar.sampler_event(kind))
        if kind == "hash":
            profiler.count_event(columnar.EVENT_HASH_OCCUPANCY,
                                 min(100, (100 * int(n_valid)) // cells))
        return fmt

    def step_chunk(c, bucket_row, accs, qhist, n_valid, n_uniq_c):
        use_fmt = chunk_plan(n_uniq_c, n_valid)
        if quantile_spec is not None:
            return _chunk_step_rle_quantile(
                jax.random.fold_in(key, c), bucket_row, n_valid,
                n_uniq_c, accs, qhist, linf_cap, l0_cap, row_clip_lo,
                row_clip_hi, middle, group_clip_lo, group_clip_hi,
                quantile_spec[1], quantile_spec[2], l1_cap,
                num_partitions=num_partitions, fmt=use_fmt,
                num_leaves=quantile_spec[0],
                need_flags=tuple(need_flags),
                has_group_clip=has_group_clip)
        return _chunk_step_rle(
            jax.random.fold_in(key, c), bucket_row, n_valid, n_uniq_c,
            accs, linf_cap, l0_cap, row_clip_lo, row_clip_hi, middle,
            group_clip_lo, group_clip_hi, l1_cap, int_clip,
            num_partitions=num_partitions, fmt=use_fmt,
            need_flags=tuple(need_flags),
            has_group_clip=has_group_clip,
            int_accumulate=int_clip is not None), qhist

    compact_step = merge_fn = None
    if (_compact_enabled(compact_merge, num_partitions)
            and quantile_spec is None
            and fmt.pid_mode == wirecodec.PID_RLE):
        max_groups = columnar.compact_group_bound(fmt.cap, fmt.ucap, l0_cap)
        if max_groups is not None:

            def compact_step(c, bucket_row, n_valid, n_uniq_c):
                use_fmt = chunk_plan(n_uniq_c, n_valid)
                return _chunk_step_rle_compact(
                    jax.random.fold_in(key, c), bucket_row, n_valid,
                    n_uniq_c, linf_cap, l0_cap, row_clip_lo, row_clip_hi,
                    middle, group_clip_lo, group_clip_hi, l1_cap, int_clip,
                    num_partitions=num_partitions, fmt=use_fmt,
                    max_groups=max_groups, need_flags=tuple(need_flags),
                    has_group_clip=has_group_clip,
                    int_accumulate=int_clip is not None)

            def merge_fn(accs, pending):
                return _merge_pending(accs, pending, num_partitions,
                                      tuple(need_flags))

    return step_chunk, compact_step, merge_fn


@functools.partial(
    jax.jit,
    static_argnames=("num_partitions", "fmt", "num_leaves", "need_flags",
                     "has_group_clip"),
    donate_argnums=(4, 5))
def _chunk_step_rle_quantile(key, row, n_valid, n_uniq, accs, qhist,
                             linf_cap, l0_cap, row_clip_lo, row_clip_hi,
                             middle, group_clip_lo, group_clip_hi,
                             q_lower, q_upper, l1_cap=None, *,
                             num_partitions: int, fmt: wirecodec.WireFormat,
                             num_leaves: int,
                             need_flags=(True, True, True, True),
                             has_group_clip: bool = True):
    """_chunk_step_rle plus the quantile-tree leaf histogram.

    Leaf counts are additive across pid-disjoint chunks, and the row keep
    mask derives from the same per-chunk PRNG key as the accumulator
    kernel, so the histogrammed contributions are exactly the rows the
    aggregation kept (columnar.bound_row_mask shares
    _sample_rows_and_groups with bound_and_aggregate).
    """
    from pipelinedp_tpu.ops import quantiles as quantile_ops
    pid, pk, value, valid, vkw = _decode_for_kernel(row, n_valid, n_uniq,
                                                    fmt)
    chunk_accs = columnar.bound_and_aggregate(
        key, pid, pk, value, valid,
        num_partitions=num_partitions,
        linf_cap=linf_cap,
        l0_cap=l0_cap,
        row_clip_lo=row_clip_lo,
        row_clip_hi=row_clip_hi,
        middle=middle,
        group_clip_lo=group_clip_lo,
        group_clip_hi=group_clip_hi,
        l1_cap=l1_cap,
        need_count=need_flags[0],
        need_sum=need_flags[1],
        need_norm=need_flags[2],
        need_norm_sq=need_flags[3],
        has_group_clip=has_group_clip,
        pid_sorted=fmt.pid_sorted,
        max_segments=fmt.ucap if fmt.pid_sorted else None,
        **vkw)
    # Same pid_sorted/tile/hash statics as the aggregation kernel, so the
    # replayed sampling decisions stay identical (shared packed-key sort
    # or hash-binned selection).
    row_keep = columnar.bound_row_mask(
        key, pid, pk, valid, linf_cap, l0_cap, l1_cap=l1_cap,
        pid_sorted=fmt.pid_sorted,
        max_segments=fmt.ucap if fmt.pid_sorted else None,
        num_partitions=num_partitions,
        tile_rows=vkw["tile_rows"], tile_slack=vkw["tile_slack"],
        hash_bins=vkw["hash_bins"], hash_bin_rows=vkw["hash_bin_rows"])
    if vkw["value_is_index"]:
        # The leaf histogram buckets float values; reconstruct with the
        # decode expression (bit-exact twin of the non-index decode).
        value = (jnp.float32(fmt.value.lo)
                 + value.astype(jnp.float32) * jnp.float32(fmt.value.scale))
    chunk_hist = quantile_ops.leaf_histograms(pk, value, row_keep,
                                              num_partitions=num_partitions,
                                              num_leaves=num_leaves,
                                              lower=q_lower, upper=q_upper)
    return _fold_chunk(accs, chunk_accs), qhist + chunk_hist


def stream_bound_and_aggregate(
    key: jax.Array,
    pid: np.ndarray,
    pk: np.ndarray,
    value: Optional[np.ndarray],
    *,
    num_partitions: int,
    linf_cap,
    l0_cap,
    row_clip_lo,
    row_clip_hi,
    middle,
    group_clip_lo,
    group_clip_hi,
    l1_cap=None,
    n_chunks: Optional[int] = None,
    value_transfer_dtype: Optional[np.dtype] = None,
    need_flags=(True, True, True, True),
    has_group_clip: bool = True,
    n_transfers: Optional[int] = None,
    transfer_encoding: str = "auto",
    quantile_spec: Optional[Tuple[int, float, float]] = None,
    resilience=None,
    resume_from=None,
    compact_merge="auto",
    segment_sort="auto",
) -> columnar.PartitionAccumulators:
    """Chunked, transfer-overlapped twin of columnar.bound_and_aggregate.

    pid: integer numpy array, any range (NOT required to be dense ids — the
      kernel only compares privacy ids for equality, so raw integer ids are
      shipped as-is after a shift-to-zero; this is what lets the engine skip
      privacy-id factorization entirely on the hot path).
    pk: dense int32 ids in [0, num_partitions).
    value: float array or None (COUNT-style).
    value_transfer_dtype: np.float16 to halve the value transfer bytes
      (opt-in: the f16 rounding of individual contributions is far below
      any DP noise scale, but it is a lossy ingest step so the caller must
      ask for it).
    transfer_encoding: "auto" (the lossless RLE/bit-plane wire codec,
      ops/wirecodec.py) or "bytes" (the legacy fixed-width byte packing).
      Both are exact; "auto" ships a fraction of the bytes.
    quantile_spec: optional (num_leaves, lower, upper) — also accumulate
      the [num_partitions, num_leaves] quantile-tree leaf histogram across
      chunks (PERCENTILE metrics on the streamed path; wire-codec
      encoding only). When set the return value is (accs, hist).
    resilience: optional runtime.StreamResilience — retry/degradation
      policy, fault injection and checkpointing for the slab loop (see
      pipelinedp_tpu/runtime/ and RESILIENCE.md). None = fail-fast, the
      historical behavior.
    resume_from: optional runtime.StreamCheckpoint to resume the slab
      loop from (fingerprint-validated; overrides any checkpoint found in
      resilience.checkpoint_policy.store). A resumed run is bit-identical
      to an uninterrupted one — per-chunk keys are fold_in(key, c) and
      accumulators are mergeable.
    compact_merge: each chunk emits compact per-group subtotal columns
      (bounded by the wire format's per-chunk pid capacity * l0_cap) and
      ONE final set of [num_partitions] scatters merges all chunks,
      instead of every chunk re-paying the full partition scatters.
      Applies to the pid-sorted wire-codec path without quantile_spec.
      "auto" (default) engages at >= COMPACT_MIN_PARTITIONS partitions —
      the regime where the [P]-output passes dominate; True forces it,
      False restores the legacy per-chunk scatters (the parity oracle).
      With group-level sum clipping active the released accumulators are
      bit-identical to the legacy path; without it they agree in exact
      arithmetic (float32 association may differ in the last ulp).
    segment_sort: the bucketed segment-local sort inside the chunk kernel
      (columnar tiled sampler; wirecodec.plan_segment_tiling), plus the
      narrow-dtype sort payload and int32 group accumulation that ride
      with it. "auto" (default) engages on the pid-sorted wire when the
      tile heuristic wins; True forces tiling whenever geometry permits;
      False restores the full round-8 kernel (global packed sort, f32
      payload, float accumulation — the parity oracle). BIT-identical
      released values in every mode — the knob is pure kernel geometry.

    Returns per-partition accumulators on device, identical in distribution
    to the single-shot kernel.
    """
    n = len(pid)
    if resume_from is not None:
        if resilience is None:
            from pipelinedp_tpu import runtime as runtime_lib
            resilience = runtime_lib.StreamResilience()
        resilience = dataclasses.replace(resilience, resume_from=resume_from)
    if quantile_spec is not None and transfer_encoding == "bytes":
        raise ValueError(
            "quantile_spec requires the wire-codec transfer encoding")
    if n == 0:
        zeros = jnp.zeros((num_partitions,), dtype=jnp.float32)
        accs0 = columnar.PartitionAccumulators(zeros, zeros, zeros, zeros,
                                               zeros)
        if quantile_spec is not None:
            return accs0, jnp.zeros((num_partitions, quantile_spec[0]),
                                    dtype=jnp.float32)
        return accs0
    k = n_chunks or _num_chunks(n)
    pid = np.asarray(pid)

    if transfer_encoding != "bytes":
        # Shared prologue with the mesh streaming path (pid-span
        # validation, width/bit planning, value plan, pid wire mode,
        # native encoder).
        with profiler.stage("dp/wire_prep"):
            enc, info = wirecodec.make_encoder(
                pid, pk, value, num_partitions=num_partitions, k=k,
                value_transfer_dtype=value_transfer_dtype)

        # `fmt` and `int_clip` are late-bound from the
        # enclosing scope: both encode branches below run
        # _finish_wire_plan before the slab loop makes the first call.
        def _finish_wire_plan(wire_fmt):
            return finish_wire_plan(
                wire_fmt, segment_sort, info.max_run,
                num_partitions=num_partitions, row_clip_lo=row_clip_lo,
                row_clip_hi=row_clip_hi, linf_cap=linf_cap,
                l1_mode=l1_cap is not None,
                group_clip_lo=group_clip_lo, group_clip_hi=group_clip_hi,
                need_flags=tuple(need_flags))

        def build_steps(fmt, int_clip):
            return _build_chunk_steps(
                key, fmt, int_clip, num_partitions=num_partitions,
                linf_cap=linf_cap, l0_cap=l0_cap, row_clip_lo=row_clip_lo,
                row_clip_hi=row_clip_hi, middle=middle,
                group_clip_lo=group_clip_lo, group_clip_hi=group_clip_hi,
                l1_cap=l1_cap, need_flags=need_flags,
                has_group_clip=has_group_clip, quantile_spec=quantile_spec,
                compact_merge=compact_merge)

        scatter_passes = 1 + sum(bool(f) for f in need_flags)

        if enc is not None:
            # Pipelined encode. Every slab shares ONE wire format (one
            # XLA compile for the chunk kernel). Three schedules, best
            # first:
            #   * PID_PLANES: no host sort at all — emit ships arrival-
            #     order pid planes, the device sorts (it sorts anyway).
            #   * PID_RLE with prep-time entry counts: the format is known
            #     before any sorting, so the per-bucket radix sort runs
            #     INSIDE the slab loop — slab s+1 sorts on the host CPU
            #     while slab s's device_put + kernels are in flight. This
            #     takes the single-core sort off the e2e critical path.
            #   * PID_RLE without entry counts (huge pid span): upfront
            #     sort to learn the RLE entry max, as before.
            with enc:
                counts = enc.counts
                cap = wirecodec._round8(int(counts.max()))
                pipelined_sort = (info.pid_mode == wirecodec.PID_RLE
                                  and enc.entry_counts is not None)
                if info.pid_mode == wirecodec.PID_PLANES:
                    fmt = wirecodec.WireFormat(
                        bytes_pid=info.bytes_pid, bits_pk=info.bits_pk,
                        cap=cap, ucap=8, value=info.plan,
                        pid_mode=wirecodec.PID_PLANES,
                        bits_pid=info.bits_pid)
                    n_uniq = np.zeros(k, dtype=np.int64)
                elif pipelined_sort:
                    n_uniq = enc.entry_counts
                    fmt = wirecodec.WireFormat(
                        bytes_pid=info.bytes_pid, bits_pk=info.bits_pk,
                        cap=cap,
                        ucap=wirecodec.round_ucap(int(n_uniq.max())),
                        value=info.plan)
                else:
                    # Distinct stage name: an upfront sort serializes
                    # ahead of the pipeline (bench reports it as
                    # non-overlapped host encode).
                    with profiler.stage("dp/wire_sort_upfront"):
                        n_uniq = enc.sort_range(0, k)
                    fmt = wirecodec.WireFormat(
                        bytes_pid=info.bytes_pid, bits_pk=info.bits_pk,
                        cap=cap,
                        ucap=wirecodec.round_ucap(int(n_uniq.max())),
                        value=info.plan)
                fmt, int_clip = _finish_wire_plan(fmt)
                budget = slab_byte_budget(pipelined_sort)
                n_t = n_transfers or _num_transfers(fmt.width * k, k,
                                                    budget)

                def prepare_slab(s0, s1):
                    if pipelined_sort:
                        with profiler.stage("dp/wire_sort"):
                            sorted_uniq = enc.sort_range(s0, s1)
                        if not np.array_equal(sorted_uniq, n_uniq[s0:s1]):
                            # Analytic prep counts must equal the
                            # post-sort RLE counts; a mismatch means
                            # corrupted input (e.g. mutated between
                            # prep and sort) and must not decode.
                            raise RuntimeError(
                                "wirecodec: prep-time RLE entry "
                                "counts disagree with the sorted "
                                "buckets")
                    with obs_trace.span("dp/wire_emit"):
                        return enc.emit_range(s0, s1, fmt)

                step_chunk, compact_step, merge_fn = build_steps(
                    fmt, int_clip)
                accs, qhist = _drive_slab_windows(
                    key, k, counts, n_uniq, fmt, prepare_slab, step_chunk,
                    n_t, num_partitions, quantile_spec, resilience,
                    lambda: _input_digest(pid, pk, value),
                    compact_step=compact_step, merge_fn=merge_fn,
                    scatter_passes=scatter_passes)
        else:
            with profiler.stage("dp/wire_encode"):
                slab, counts, n_uniq, fmt = wirecodec.encode_buckets_numpy(
                    pid, pk, value, pid_lo=info.pid_lo, k=k,
                    bytes_pid=info.bytes_pid, bits_pk=info.bits_pk,
                    plan=info.plan, pid_mode=info.pid_mode,
                    bits_pid=info.bits_pid)
            fmt, int_clip = _finish_wire_plan(fmt)
            n_t = n_transfers or _num_transfers(slab.nbytes, k)
            step_chunk, compact_step, merge_fn = build_steps(
                fmt, int_clip)
            accs, qhist = _drive_slab_windows(
                key, k, counts, n_uniq, fmt,
                lambda s0, s1: slab[s0:s1], step_chunk,
                n_t, num_partitions, quantile_spec, resilience,
                lambda: _input_digest(pid, pk, value),
                compact_step=compact_step, merge_fn=merge_fn,
                scatter_passes=scatter_passes)
        if quantile_spec is not None:
            return accs, qhist
        return accs

    # Legacy fixed-width byte packing (explicit transfer_encoding="bytes").
    pid_lo = int(pid.min())
    pid_span = int(pid.max()) - pid_lo
    if pid_span >= np.iinfo(np.int32).max - 1:
        raise ValueError(
            f"privacy-id span {pid_span} does not fit int32; factorize the "
            f"ids to dense int32 before streaming")
    bytes_pid = _int_bytes(pid_span)
    bytes_pk = _int_bytes(max(num_partitions - 1, 0))
    value_f16 = (value_transfer_dtype is not None
                 and np.dtype(value_transfer_dtype) == np.float16)
    bytes_value = 2 if value_f16 else 4
    width = bytes_pid + bytes_pk + bytes_value
    packed = _pack_native(pid, pk, value, pid_lo, k, bytes_pid, bytes_pk,
                          value_f16, width)
    if packed is None:
        packed = _pack_numpy(pid, pk, value, pid_lo, k, bytes_pid, bytes_pk,
                             value_f16, width, bytes_value)
    buckets, counts = packed

    # Transfers go in a few large slabs while execution stays per-bucket
    # (device slices of the slab): each host->device put pays a fixed
    # cost, which would eat the pipeline if every bucket shipped
    # separately, and the slab after
    # this one still overlaps the current slab's kernels (async dispatch).
    n_t = n_transfers or _num_transfers(buckets.nbytes, k)

    bytes_event = columnar.sampler_event(columnar.sampler_kind(
        int(buckets.shape[1]), num_partitions=num_partitions,
        max_segments=None, pid_sorted=False, l1_mode=l1_cap is not None,
        tile_rows=0, tile_slack=0, hash_bins=0, hash_bin_rows=0))

    def step_chunk_bytes(c, bucket_row, accs, qhist, n_valid, _n_uniq_c):
        profiler.count_event(bytes_event)
        return _chunk_step(jax.random.fold_in(key, c), bucket_row,
                           n_valid, accs,
                           linf_cap, l0_cap, row_clip_lo,
                           row_clip_hi, middle, group_clip_lo,
                           group_clip_hi, l1_cap,
                           num_partitions=num_partitions,
                           bytes_pid=bytes_pid,
                           bytes_pk=bytes_pk,
                           value_f16=value_f16,
                           need_flags=tuple(need_flags),
                           has_group_clip=has_group_clip), qhist

    accs, _ = _drive_slab_windows(
        key, k, counts, None,
        ("bytes", bytes_pid, bytes_pk, value_f16, width),
        lambda s0, s1: buckets[s0:s1], step_chunk_bytes,
        n_t, num_partitions, None, resilience,
        lambda: _input_digest(pid, pk, value),
        scatter_passes=1 + sum(bool(f) for f in need_flags))
    return accs


def input_digest(pid, pk, value) -> str:
    """Content digest of one (pid, pk, value) column triple — the same
    identity ``ResidentWire.data_digest`` carries, exposed for callers
    that digest batches before ingesting them (the serving append WAL
    keys its idempotency on this)."""
    from pipelinedp_tpu.runtime import checkpoint as checkpoint_lib

    return checkpoint_lib.array_digest(pid, pk, value)


_input_digest = input_digest


def _snapshot_host(accs, qhist):
    """Host copies of the slab-loop accumulator state for a checkpoint
    snapshot (shared by the single-device and mesh placements)."""
    # dplint: disable=DPL007 — checkpoint snapshot of pre-noise accumulators: never released, consumed only by fingerprint-validated resume (RESILIENCE.md)
    host_accs, host_q = jax.device_get((tuple(accs), qhist))
    return (tuple(np.asarray(a) for a in host_accs),
            None if host_q is None else np.asarray(host_q))


class _SingleDevicePlacement(driver_lib.DevicePlacement):
    """Single-device strategy for the unified slab driver
    (runtime/driver.py owns the loop; this class owns how slabs land on
    the one device and how chunk steps fold).

    The chunk steps (``_chunk_step*``) donate the accumulator buffers
    into the kernel — five distinct zero buffers at init, fresh host
    copies on restore, so donated buffers are never aliased — and device
    OOM is recoverable by halving the slab window (the slab byte budget
    is ours to choose, unlike the mesh's fixed chunk granularity).
    """

    stage_prefix = "dp/stream_slab_"
    prefetch_prefix = "pdp-slab-prefetch"
    degradable = True
    donates = True

    def __init__(self, *, num_partitions, counts, n_uniq, step_chunk,
                 compact_step=None, merge_fn=None, quantile_leaves=None):
        self._num_partitions = num_partitions
        self._counts = counts
        self._n_uniq = n_uniq
        self._step_chunk = step_chunk
        self._compact_fn = compact_step
        self._merge_fn = merge_fn
        self._quantile_leaves = quantile_leaves
        self.compact = compact_step is not None and merge_fn is not None

    def init_state(self):
        # Five distinct buffers: the accumulators are donated into each
        # chunk step, and a donated buffer must not be aliased.
        accs = columnar.PartitionAccumulators(
            *(jnp.zeros((self._num_partitions,), dtype=jnp.float32)
              for _ in range(5)))
        qhist = (jnp.zeros((self._num_partitions, self._quantile_leaves),
                           dtype=jnp.float32)
                 if self._quantile_leaves is not None else None)
        return accs, qhist

    def transfer(self, slab, s0, s1):
        return jax.device_put(slab)

    def _chunk_meta(self, c):
        n_valid = int(self._counts[c])
        n_uniq_c = int(self._n_uniq[c]) if self._n_uniq is not None else 0
        return n_valid, n_uniq_c

    def step(self, c, payload, offset, accs, qhist):
        n_valid, n_uniq_c = self._chunk_meta(c)
        return self._step_chunk(c, payload[offset], accs, qhist, n_valid,
                                n_uniq_c)

    def compact_step(self, c, payload, offset):
        n_valid, n_uniq_c = self._chunk_meta(c)
        return self._compact_fn(c, payload[offset], n_valid, n_uniq_c)

    def merge_pending(self, accs, pending):
        return self._merge_fn(accs, pending)

    def snapshot(self, accs, qhist):
        # dplint: disable=DPL007 — checkpoint snapshot of pre-noise accumulators: never released, consumed only by fingerprint-validated resume (RESILIENCE.md; same by-design transfer _snapshot_host suppresses)
        return _snapshot_host(accs, qhist)

    def restore(self, cp, expects_qhist):
        return _restore_checkpoint(cp, expects_qhist=expects_qhist)


def _drive_slab_windows(key, k, counts, n_uniq, fmt_desc, prepare_slab,
                        step_chunk, n_transfers, num_partitions,
                        quantile_spec, resilience, data_digest_fn=None, *,
                        compact_step=None, merge_fn=None, scatter_passes=5):
    """Runs the single-device streaming schedule on the unified slab
    driver (runtime.SlabDriver — checkpoint/resume, retry + OOM window
    degradation, lookahead prefetch, compact merge, fault injection and
    the dispatch watchdog all live there, shared with the mesh path).

    ``prepare_slab(s0, s1)`` produces the host slab (sort+emit for the
    native codec, an array slice otherwise) and
    ``step_chunk(c, row, accs, qhist, n_valid, n_uniq_c)`` folds each
    chunk into the running accumulators with its ``fold_in(key, c)``
    key. Returns (accs, qhist); qhist is None when quantile_spec is
    None.
    """
    placement = _SingleDevicePlacement(
        num_partitions=num_partitions, counts=counts, n_uniq=n_uniq,
        step_chunk=step_chunk, compact_step=compact_step,
        merge_fn=merge_fn,
        quantile_leaves=(quantile_spec[0] if quantile_spec is not None
                         else None))
    plan = driver_lib.SlabPlan(
        n_chunks=k,
        window_chunks=max(1, (k + n_transfers - 1) // n_transfers),
        fmt_desc=repr(fmt_desc),
        counts=counts,
        n_uniq=n_uniq,
        scatter_passes=scatter_passes,
        quantile=quantile_spec is not None,
        data_digest_fn=data_digest_fn,
        prefetch_depth=prefetch_depth())
    return driver_lib.SlabDriver(placement, plan, prepare_slab, key,
                                 resilience).run()


def _restore_checkpoint(cp, expects_qhist: bool = False):
    """(accs, qhist) device state from a validated checkpoint. Fresh
    host copies, so restored buffers never alias store state even after
    the chunk steps donate them."""
    from pipelinedp_tpu.runtime import checkpoint as checkpoint_lib

    if expects_qhist and cp.qhist is None:
        raise checkpoint_lib.CheckpointMismatchError(
            "checkpoint has no quantile histogram but this run streams "
            "PERCENTILE metrics")
    accs = columnar.PartitionAccumulators(
        *(jnp.asarray(np.array(a)) for a in cp.accs))
    qhist = None if cp.qhist is None else jnp.asarray(np.array(cp.qhist))
    return accs, qhist


# Log the native-packer fallback once per process, not once per call
# (count_event("runtime/native_fallback") keeps the per-call tally).
_native_fallback_logged = False


def _count_native_fallback(reason: str) -> None:
    global _native_fallback_logged
    profiler.count_event("runtime/native_fallback")
    if not _native_fallback_logged:
        _native_fallback_logged = True
        logging.info(
            "pipelinedp_tpu streaming: native row packer unavailable (%s); "
            "using the numpy fallback", reason)


def _pack_native(pid, pk, value, pid_lo, k, bytes_pid, bytes_pk, value_f16,
                 width):
    """One multithreaded C++ pass: bucket + byte-pack all rows.

    Returns ([bucket buffers], counts) or None when the native library is
    unavailable or the dtypes don't qualify (the numpy fallback handles
    everything).
    """
    from pipelinedp_tpu.native import loader
    try:
        lib = loader.load_row_packer()
    except loader.LOADER_ERRORS as e:
        # Only loader/codec failures fall back (the packer is an
        # optimization); anything else — including NativeRequiredError
        # under PIPELINEDP_TPU_REQUIRE_NATIVE=1 — propagates.
        _count_native_fallback(f"{type(e).__name__}: {e}")
        return None
    if lib is None:
        _count_native_fallback("build/load failed; see native loader logs")
        return None
    import ctypes

    n = len(pid)
    pid32 = np.ascontiguousarray(pid, dtype=np.int32)
    pk32 = np.ascontiguousarray(pk, dtype=np.int32)
    val32 = (np.ascontiguousarray(value, dtype=np.float32)
             if value is not None else None)
    # Knuth-hashed buckets are near-uniform: pad 2% + slack, retry once
    # with the exact max if an adversarial id distribution overflows.
    cap = n // k + max(n // (k * 50), 4096)
    out = None
    for attempt in range(2):
        # Drop the undersized buffer before allocating the retry size, so
        # peak host RAM stays ~1x the packed input even on skewed ids.
        del out
        out = np.zeros((k, cap, width), dtype=np.uint8)
        counts = np.zeros(k, dtype=np.int64)
        rc = lib.pdp_pack_buckets(
            pid32.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            pk32.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            val32.ctypes.data_as(ctypes.c_void_p) if val32 is not None
            else None, n, int(pid_lo), k, bytes_pid, bytes_pk,
            int(value_f16),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap,
            counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        if rc == 0:
            return out, counts
        if rc == 2:
            new_cap = int(counts.max())
            logging.warning(
                "pipelinedp_tpu streaming: bucket capacity %d overflowed "
                "(skewed privacy-id distribution; max bucket %d rows); "
                "retrying with the exact size.", cap, new_cap)
            cap = new_cap
            continue
        return None
    return None


def _pack_numpy(pid, pk, value, pid_lo, k, bytes_pid, bytes_pk, value_f16,
                width, bytes_value):
    """Numpy fallback: same [k, cap, width] buckets and byte layout as the
    native packer."""
    shifted = (pid - pid_lo).astype(np.uint32, copy=False)
    bucket = ((shifted * _HASH_MULT) >> np.uint32(16)) % np.uint32(k)
    counts = np.bincount(bucket, minlength=k).astype(np.int64)
    chunk_rows = int(counts.max()) if k else 1
    if value is not None:
        value = np.asarray(value)
        value = value.astype(np.float16 if value_f16 else np.float32,
                             copy=False)
    out = np.zeros((k, chunk_rows, width), dtype=np.uint8)
    for c in range(k):
        idx = np.flatnonzero(bucket == c)
        buf = out[c]
        m = len(idx)
        _pack_ints(buf[:m], shifted[idx], 0, bytes_pid)
        _pack_ints(buf[:m], pk[idx].astype(np.uint32, copy=False),
                   bytes_pid, bytes_pk)
        if value is not None:
            buf[:m, bytes_pid + bytes_pk:] = (
                value[idx].view(np.uint8).reshape(m, bytes_value))
    return out, counts


# ---------------------------------------------------------------------------
# Resident-dataset wire: pay encode + sort once, serve many queries
# (pipelinedp_tpu/serving/; SERVING.md).
# ---------------------------------------------------------------------------

# Profiler event counters of the serving replay paths
# (profiler.count_event / event_count):
#   EVENT_SERVING_LAUNCHES — chunk-kernel dispatches issued by the replay
#     paths; a batched launch covering B configs counts ONCE (the
#     structural evidence that B configs share one launch);
#   EVENT_SERVING_REPLAYS — resident-wire replays executed (cache misses
#     at the session layer land here).
EVENT_SERVING_LAUNCHES = "serving/kernel_dispatches"
EVENT_SERVING_REPLAYS = "serving/wire_replays"


@dataclasses.dataclass
class ResidentWire:
    """The reusable product of one wire-pipeline pass over a dataset.

    Holds the sorted, wire-codec-encoded chunk slab (host copy always;
    device copy on demand) plus everything a chunk kernel needs to run
    over it: per-bucket row counts, RLE entry counts, the BASE wire
    format (no tile geometry — ``finish_wire_plan`` resolves the
    query-dependent sort geometry per replay), and the prep-time max
    single-pid run that sizes tile slack.

    The handle is immutable after ingest. ``fingerprint`` names it —
    chunk count, format, per-bucket counts and the source-column digest
    (wirecodec.resident_fingerprint) — so a serving session can refuse a
    source dataset that was mutated after ingest.

    Replaying the handle under a key is bit-identical to streaming the
    source columns cold with the same key and chunk count: the slab
    bytes are the same bytes ``stream_bound_and_aggregate`` would have
    encoded, and the replay folds them through the same chunk kernels
    under the same ``fold_in(key, c)`` schedule.
    """
    slab: np.ndarray  # [k, width] uint8 — the sorted wire chunks
    counts: np.ndarray  # [k] rows per bucket
    n_uniq: np.ndarray  # [k] RLE entries per bucket (zeros for planes)
    fmt: wirecodec.WireFormat  # base format (tile-free)
    max_run: int  # prep-time max single-pid run (-1 = unknown)
    num_partitions: int
    n_rows: int
    n_dev: int = 1  # buckets per chunk (mesh ingest: mesh device count)
    data_digest: str = ""
    fingerprint: str = ""
    _device_slab: Optional[jax.Array] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def k(self) -> int:
        """Total wire buckets."""
        return int(len(self.counts))

    @property
    def n_chunks(self) -> int:
        """Chunk-key positions (mesh chunks span n_dev buckets)."""
        return self.k // max(self.n_dev, 1)

    @property
    def host_nbytes(self) -> int:
        return int(self.slab.nbytes) if self.slab is not None else 0

    @property
    def device_nbytes(self) -> int:
        if self._device_slab is None or self.slab is None:
            return 0
        return int(self.slab.nbytes)

    @property
    def device_resident(self) -> bool:
        return self._device_slab is not None

    @property
    def loaded(self) -> bool:
        """Whether the slab bytes are in memory (False after ``unload``;
        the serving SessionManager's disk-spill rung)."""
        return self.slab is not None

    def unload(self) -> None:
        """Frees the slab bytes (host AND device) while keeping every
        piece of metadata — counts, format, fingerprint — so a spilled
        handle can be digest-validated back in with :meth:`reload`.
        Replaying an unloaded handle is a caller bug (the serving layer
        re-hydrates before it replays)."""
        self._device_slab = None
        self.slab = None

    def reload(self, slab: np.ndarray) -> None:
        """Restores the slab bytes of an unloaded handle. The caller
        (serving/store.py) has already digest-validated the bytes
        against the fingerprint; this only guards the geometry."""
        slab = np.asarray(slab)
        expected = (self.k, self.fmt.width)
        if slab.shape != expected or slab.dtype != np.uint8:
            raise ValueError(
                f"reload geometry mismatch: got {slab.dtype}{slab.shape}, "
                f"handle expects uint8{expected}")
        self.slab = slab

    def ensure_device(self):
        """Device copy of the whole slab (single-device handles only);
        idempotent. Replays then slice it instead of re-transferring."""
        if self.n_dev != 1:
            raise ValueError(
                "device residency applies to single-device handles; mesh "
                "replays ship each chunk sharded per query")
        if self.slab is None:
            raise ValueError(
                "handle is unloaded (spilled); reload it before asking "
                "for device residency")
        if self._device_slab is None:
            self._device_slab = jax.device_put(self.slab)
        return self._device_slab

    def drop_device(self) -> None:
        """Frees the device copy (the host slab stays authoritative)."""
        self._device_slab = None


class _IngestPlacement(driver_lib.DevicePlacement):
    """No-op placement for retain-wire ingest: the driver runs the host
    encode schedule (prefetch pool, watchdog, fault injection) and the
    retain sink keeps every prepared slab; nothing lands on a device and
    no chunk kernels run."""

    stage_prefix = "dp/ingest_slab_"
    prefetch_prefix = "pdp-ingest-prefetch"
    degradable = False
    donates = False
    ships_host_bytes = False

    def init_state(self):
        return None, None

    def transfer(self, slab, s0, s1):
        return slab

    def step(self, c, payload, offset, accs, qhist):
        return accs, qhist

    def snapshot(self, accs, qhist):
        return (), None

    def restore(self, cp, expects_qhist):
        return None, None

    def sync(self, accs, qhist, pending):
        pass


def _empty_resident_wire(num_partitions: int) -> ResidentWire:
    fmt = wirecodec.WireFormat(
        bytes_pid=1,
        bits_pk=max(1, int(max(num_partitions - 1, 0)).bit_length()),
        cap=8, ucap=8, value=wirecodec.ValuePlan(wirecodec.VALUE_NONE))
    counts = np.zeros(0, dtype=np.int64)
    n_uniq = np.zeros(0, dtype=np.int64)
    digest = _input_digest(np.zeros(0, np.int32), np.zeros(0, np.int32),
                           None)
    return ResidentWire(
        slab=np.zeros((0, fmt.width), dtype=np.uint8), counts=counts,
        n_uniq=n_uniq, fmt=fmt, max_run=0, num_partitions=num_partitions,
        n_rows=0, data_digest=digest,
        fingerprint=wirecodec.resident_fingerprint(0, fmt, counts, n_uniq,
                                                   digest))


def ingest_resident_wire(pid: np.ndarray,
                         pk: np.ndarray,
                         value: Optional[np.ndarray],
                         *,
                         num_partitions: int,
                         n_chunks: Optional[int] = None,
                         n_dev: int = 1,
                         value_transfer_dtype=None,
                         n_transfers: Optional[int] = None,
                         resilience=None) -> ResidentWire:
    """Runs the wire pipeline once — encode, per-bucket radix sort, emit —
    and RETAINS the sorted chunks instead of discarding them after the
    fold (the SlabDriver's retain-wire mode).

    The schedule is byte-identical to what stream_bound_and_aggregate
    (n_dev == 1) or the mesh streaming path (n_dev == mesh device count)
    would have encoded for the same chunk count, so replaying the handle
    is bit-identical to the cold path. No chunk kernels run: ingest is
    pure host encode (multithreaded native sort + lookahead prefetch)
    plus one pass of the slab loop with no-op steps.
    """
    if (resilience is not None
            and getattr(resilience, "checkpoint_policy", None) is not None):
        raise ValueError(
            "ingest does not checkpoint (it folds no accumulators); give "
            "the checkpoint policy to the queries, not the ingest")
    pid = np.asarray(pid)
    n = len(pid)
    if n == 0:
        return _empty_resident_wire(num_partitions)
    if n_dev > 1:
        n_c = n_chunks or _num_chunks(max(n // n_dev, 1))
        k = n_c * n_dev
    else:
        k = n_chunks or _num_chunks(n)
    with profiler.stage("dp/wire_prep"):
        enc, info = wirecodec.make_encoder(
            pid, pk, value, num_partitions=num_partitions, k=k,
            value_transfer_dtype=value_transfer_dtype)
    if enc is None:
        with profiler.stage("dp/wire_encode"):
            slab, counts, n_uniq, fmt = wirecodec.encode_buckets_numpy(
                pid, pk, value, pid_lo=info.pid_lo, k=k,
                bytes_pid=info.bytes_pid, bits_pk=info.bits_pk,
                plan=info.plan, pid_mode=info.pid_mode,
                bits_pid=info.bits_pid)
        slab = np.ascontiguousarray(slab)
    else:
        with enc:
            counts = enc.counts
            cap = wirecodec._round8(int(counts.max()))
            pipelined_sort = (info.pid_mode == wirecodec.PID_RLE
                              and enc.entry_counts is not None)
            if info.pid_mode == wirecodec.PID_PLANES:
                fmt = wirecodec.WireFormat(
                    bytes_pid=info.bytes_pid, bits_pk=info.bits_pk,
                    cap=cap, ucap=8, value=info.plan,
                    pid_mode=wirecodec.PID_PLANES, bits_pid=info.bits_pid)
                n_uniq = np.zeros(k, dtype=np.int64)
            elif pipelined_sort:
                n_uniq = enc.entry_counts
                fmt = wirecodec.WireFormat(
                    bytes_pid=info.bytes_pid, bits_pk=info.bits_pk,
                    cap=cap, ucap=wirecodec.round_ucap(int(n_uniq.max())),
                    value=info.plan)
            else:
                with profiler.stage("dp/wire_sort_upfront"):
                    n_uniq = enc.sort_range(0, k)
                fmt = wirecodec.WireFormat(
                    bytes_pid=info.bytes_pid, bits_pk=info.bits_pk,
                    cap=cap, ucap=wirecodec.round_ucap(int(n_uniq.max())),
                    value=info.plan)

            def prepare_slab(s0, s1):
                if pipelined_sort:
                    with profiler.stage("dp/wire_sort"):
                        sorted_uniq = enc.sort_range(s0, s1)
                    if not np.array_equal(sorted_uniq, n_uniq[s0:s1]):
                        raise RuntimeError(
                            "wirecodec: prep-time RLE entry counts "
                            "disagree with the sorted buckets")
                with obs_trace.span("dp/wire_emit"):
                    return enc.emit_range(s0, s1, fmt)

            slab = np.zeros((k, fmt.width), dtype=np.uint8)

            def retain(s0, s1, window_slab):
                slab[s0:s1] = window_slab

            budget = slab_byte_budget(pipelined_sort)
            n_t = n_transfers or _num_transfers(fmt.width * k, k, budget)
            plan = driver_lib.SlabPlan(
                n_chunks=k,
                window_chunks=max(1, (k + n_t - 1) // n_t),
                fmt_desc=repr(fmt),
                counts=counts,
                n_uniq=n_uniq,
                scatter_passes=0,
                retain_sink=retain,
                prefetch_depth=prefetch_depth())
            driver_lib.SlabDriver(_IngestPlacement(), plan, prepare_slab,
                                  None, resilience).run()
    digest = _input_digest(pid, pk, value)
    counts = np.asarray(counts, dtype=np.int64)
    n_uniq = np.asarray(n_uniq, dtype=np.int64)
    return ResidentWire(
        slab=slab, counts=counts, n_uniq=n_uniq, fmt=fmt,
        max_run=info.max_run, num_partitions=num_partitions, n_rows=n,
        n_dev=n_dev, data_digest=digest,
        fingerprint=wirecodec.resident_fingerprint(k, fmt, counts, n_uniq,
                                                   digest))


class _ResidentReplayPlacement(_SingleDevicePlacement):
    """Single-device placement replaying a retained wire: when the
    handle holds a device copy of the slab the transfer is a device-side
    slice (no host->device bytes at all); otherwise the host slab window
    ships like a cold slab. Chunk dispatches credit the serving launch
    counter."""

    stage_prefix = "dp/replay_slab_"
    prefetch_prefix = "pdp-replay-prefetch"

    def __init__(self, device_slab=None, **kw):
        super().__init__(**kw)
        self._device_slab = device_slab

    @property
    def ships_host_bytes(self) -> bool:
        return self._device_slab is None

    def transfer(self, slab, s0, s1):
        if self._device_slab is not None:
            return self._device_slab[s0:s1]
        return jax.device_put(slab)

    def step(self, c, payload, offset, accs, qhist):
        profiler.count_event(EVENT_SERVING_LAUNCHES)
        return super().step(c, payload, offset, accs, qhist)

    def compact_step(self, c, payload, offset):
        profiler.count_event(EVENT_SERVING_LAUNCHES)
        return super().compact_step(c, payload, offset)


def _zero_accs(num_partitions: int, quantile_spec):
    zeros = jnp.zeros((num_partitions,), dtype=jnp.float32)
    accs = columnar.PartitionAccumulators(zeros, zeros, zeros, zeros, zeros)
    if quantile_spec is not None:
        return accs, jnp.zeros((num_partitions, quantile_spec[0]),
                               dtype=jnp.float32)
    return accs, None


def replay_resident_wire(key: jax.Array,
                         wire: ResidentWire,
                         *,
                         linf_cap,
                         l0_cap,
                         row_clip_lo,
                         row_clip_hi,
                         middle,
                         group_clip_lo,
                         group_clip_hi,
                         l1_cap=None,
                         need_flags=(True, True, True, True),
                         has_group_clip: bool = True,
                         quantile_spec: Optional[Tuple[int, float,
                                                       float]] = None,
                         segment_sort="auto",
                         compact_merge="auto",
                         n_transfers: Optional[int] = None,
                         resilience=None):
    """Answers one query from a retained wire: kernel + fold only — no
    encode, no sort, and (device-resident handles) no transfer.

    Bit-identical to stream_bound_and_aggregate(key, <source columns>,
    n_chunks=wire.n_chunks, ...) with the same knobs: the same chunk
    kernels fold the same slab bytes under the same ``fold_in(key, c)``
    schedule (shared _build_chunk_steps). Returns accs, or (accs, qhist)
    when quantile_spec is set.
    """
    if wire.n_dev != 1:
        raise ValueError(
            "this handle was ingested for a mesh; replay it through "
            "parallel.sharded.replay_resident_wire")
    num_partitions = wire.num_partitions
    if wire.n_rows == 0:
        accs, qhist = _zero_accs(num_partitions, quantile_spec)
        return (accs, qhist) if quantile_spec is not None else accs
    profiler.count_event(EVENT_SERVING_REPLAYS)
    obs_trace.event("wire_replay", n_chunks=wire.n_chunks,
                    device_resident=wire.device_resident)
    fmt, int_clip = finish_wire_plan(
        wire.fmt, segment_sort, wire.max_run,
        num_partitions=num_partitions, row_clip_lo=row_clip_lo,
        row_clip_hi=row_clip_hi, linf_cap=linf_cap,
        l1_mode=l1_cap is not None,
        group_clip_lo=group_clip_lo, group_clip_hi=group_clip_hi,
        need_flags=tuple(need_flags))
    step_chunk, compact_step, merge_fn = _build_chunk_steps(
        key, fmt, int_clip, num_partitions=num_partitions,
        linf_cap=linf_cap, l0_cap=l0_cap, row_clip_lo=row_clip_lo,
        row_clip_hi=row_clip_hi, middle=middle,
        group_clip_lo=group_clip_lo, group_clip_hi=group_clip_hi,
        l1_cap=l1_cap, need_flags=need_flags,
        has_group_clip=has_group_clip, quantile_spec=quantile_spec,
        compact_merge=compact_merge)
    k = wire.k
    placement = _ResidentReplayPlacement(
        device_slab=wire._device_slab,
        num_partitions=num_partitions, counts=wire.counts,
        n_uniq=wire.n_uniq, step_chunk=step_chunk,
        compact_step=compact_step, merge_fn=merge_fn,
        quantile_leaves=(quantile_spec[0] if quantile_spec is not None
                         else None))
    n_t = n_transfers or _num_transfers(wire.slab.nbytes, k)
    plan = driver_lib.SlabPlan(
        n_chunks=k,
        window_chunks=max(1, (k + n_t - 1) // n_t),
        fmt_desc=repr(fmt),
        counts=wire.counts,
        n_uniq=wire.n_uniq,
        scatter_passes=1 + sum(bool(f) for f in need_flags),
        quantile=quantile_spec is not None)
    accs, qhist = driver_lib.SlabDriver(
        placement, plan, lambda s0, s1: wire.slab[s0:s1], key,
        resilience).run()
    if quantile_spec is not None:
        return accs, qhist
    return accs


@functools.partial(
    jax.jit,
    static_argnames=("num_partitions", "fmt", "need_flags",
                     "has_group_clip"))
def _chunk_step_rle_batch(c, keys, row, n_valid, n_uniq_c, accs, linf_caps,
                          l0_caps, row_clip_los, row_clip_his, middles,
                          group_clip_los, group_clip_his, l1_caps=None, *,
                          num_partitions: int, fmt: wirecodec.WireFormat,
                          need_flags=(True, True, True, True),
                          has_group_clip: bool = True):
    """One wire chunk folded for a whole BATCH of query configs in one
    launch: the chunk is decoded once, then the bounding kernel vmaps
    over the per-config (key, caps, clip bounds) with the decoded rows
    broadcast. Accumulators are [B, num_partitions].

    Per-config results are the same values the unbatched
    ``_chunk_step_rle`` produces for that config alone (the sampling
    sorts are exact and the per-config accumulations are independent
    lanes of the batched kernel); the per-config key schedule is the
    engine's own ``fold_in(key_b, c)``.

    ``l1_caps`` (per-config total-contribution caps, [B] int32 or None)
    rides an extra vmapped lane; None keeps the l1-free kernel shape.
    """
    pid, pk, value, valid, vkw = _decode_for_kernel(row, n_valid, n_uniq_c,
                                                    fmt)

    def one(key, acc, linf_cap, l0_cap, row_clip_lo, row_clip_hi, middle,
            group_clip_lo, group_clip_hi, l1_cap=None):
        chunk_accs = columnar.bound_and_aggregate(
            jax.random.fold_in(key, c), pid, pk, value, valid,
            num_partitions=num_partitions,
            linf_cap=linf_cap,
            l0_cap=l0_cap,
            row_clip_lo=row_clip_lo,
            row_clip_hi=row_clip_hi,
            middle=middle,
            group_clip_lo=group_clip_lo,
            group_clip_hi=group_clip_hi,
            l1_cap=l1_cap,
            need_count=need_flags[0],
            need_sum=need_flags[1],
            need_norm=need_flags[2],
            need_norm_sq=need_flags[3],
            has_group_clip=has_group_clip,
            pid_sorted=fmt.pid_sorted,
            max_segments=fmt.ucap if fmt.pid_sorted else None,
            **vkw)
        return _fold_chunk(acc, chunk_accs)

    if l1_caps is not None:
        return jax.vmap(one)(keys, accs, linf_caps, l0_caps, row_clip_los,
                             row_clip_his, middles, group_clip_los,
                             group_clip_his, l1_caps)
    return jax.vmap(one)(keys, accs, linf_caps, l0_caps, row_clip_los,
                         row_clip_his, middles, group_clip_los,
                         group_clip_his)


def replay_resident_wire_batched(keys,
                                 wire: ResidentWire,
                                 *,
                                 linf_caps,
                                 l0_caps,
                                 row_clip_los,
                                 row_clip_his,
                                 middles,
                                 group_clip_los,
                                 group_clip_his,
                                 l1_caps=None,
                                 need_flags=(True, True, True, True),
                                 has_group_clip: bool = True,
                                 n_transfers: Optional[int] = None
                                 ) -> columnar.PartitionAccumulators:
    """Folds the retained wire for B query configs in ONE launch per
    chunk: configs that share the sorted wire but differ in caps / clip
    bounds / keys ride a vmapped bounding kernel instead of B sequential
    passes over the same bytes.

    keys: sequence of B chunk-kernel keys (one per config, the engine's
    k_kernel); caps/bounds: length-B sequences. Returns [B,
    num_partitions] PartitionAccumulators. Per-config lanes match the
    config's sequential replay (and therefore its cold run): the batched
    kernel uses the parity-oracle statics — untiled packed sort, float32
    payload and accumulation — which PR 7 pins bit-identical to every
    other segment_sort mode.
    """
    num_partitions = wire.num_partitions
    B = len(linf_caps)
    if wire.n_dev != 1:
        raise ValueError("batched replay supports single-device handles")
    keys = jnp.stack([jnp.asarray(k) for k in keys])
    accs = columnar.PartitionAccumulators(
        *(jnp.zeros((B, num_partitions), dtype=jnp.float32)
          for _ in range(5)))
    if wire.n_rows == 0:
        return accs
    profiler.count_event(EVENT_SERVING_REPLAYS)
    # Parity-oracle statics: tile-free packed sort, wide payload, no
    # hash bins. PR 7's parity matrix pins the sorted segment_sort modes
    # bit-identical (and the hash-binned stage matches them under its
    # exactness gate — the only regime the auto dispatch picks it in),
    # so the batched lanes match sequential replays at any knob setting.
    fmt = dataclasses.replace(wire.fmt, tile_rows=0, tile_slack=0,
                              hash_bins=0, hash_bin_rows=0,
                              sort_value_narrow=False)
    linf = jnp.asarray(np.asarray(linf_caps, dtype=np.int32))
    l0 = jnp.asarray(np.asarray(l0_caps, dtype=np.int32))
    rlo = jnp.asarray(np.asarray(row_clip_los, dtype=np.float32))
    rhi = jnp.asarray(np.asarray(row_clip_his, dtype=np.float32))
    mid = jnp.asarray(np.asarray(middles, dtype=np.float32))
    glo = jnp.asarray(np.asarray(group_clip_los, dtype=np.float32))
    ghi = jnp.asarray(np.asarray(group_clip_his, dtype=np.float32))
    l1 = (None if l1_caps is None
          else jnp.asarray(np.asarray(l1_caps, dtype=np.int32)))
    k = wire.k
    n_t = n_transfers or _num_transfers(wire.slab.nbytes, k)
    window = max(1, (k + n_t - 1) // n_t)
    kind_event = columnar.sampler_event(wire_sampler_kind(
        fmt, num_partitions=num_partitions, l1_mode=l1 is not None))
    for s0 in range(0, k, window):
        s1 = min(s0 + window, k)
        if wire._device_slab is not None:
            payload = wire._device_slab[s0:s1]
        else:
            payload = jax.device_put(wire.slab[s0:s1])
        for c in range(s0, s1):
            accs = _chunk_step_rle_batch(
                c, keys, payload[c - s0], int(wire.counts[c]),
                int(wire.n_uniq[c]), accs, linf, l0, rlo, rhi, mid, glo,
                ghi, l1, num_partitions=num_partitions, fmt=fmt,
                need_flags=tuple(need_flags),
                has_group_clip=has_group_clip)
            # ONE launch covers all B configs.
            profiler.count_event(EVENT_SERVING_LAUNCHES)
            profiler.count_event(kind_event)
    return accs
