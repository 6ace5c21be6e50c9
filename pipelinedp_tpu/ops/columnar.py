"""The fused columnar DP-aggregation kernels.

This is the TPU-native replacement for the reference's per-row dataflow
(contribution_bounders.py + combiners.py + the per-key shuffle of
pipeline_backend.py): the whole bound-and-aggregate stage is ONE sort plus a
handful of segment reductions over fixed-shape arrays, entirely inside jit.

Dataflow (bound_and_aggregate):
  1. lexsort rows by (privacy_id, group_hash, partition_key, uniform),
     where group_hash is a keyed 32-bit mix of (pid, pk): within each
     privacy id the (pid, pk) groups land in hash order — a uniform random
     permutation of the groups — and within each group the rows land in
     uniform-tiebreak order. One sort therefore provides BOTH sampling
     permutations (the reference's two sample_fixed_per_key passes).
  2. rank rows within (pid, pk) via a cummax over group-start indices; keep
     rank < max_contributions_per_partition  (Linf bounding).
  3. rank groups within pid via the group counter minus its value at the
     pid's first row; keep rank < max_partitions_contributed (L0 bounding)
     — no second sort: group order within a pid is already random.
  4. reduce rows -> (pid, pk) group accumulators with per-column
     segment-sums over the sorted (hence monotone) group ids.
  5. reduce kept groups -> per-partition accumulators (count, clipped sum,
     normalized sum, normalized sum of squares, privacy-id count) with
     per-column segment-sums into [num_partitions] arrays.

The round-4 profile attributed the kernel plateau to pass count, not sort
cost (each 100M-row segment-sum/gather is a full HBM round trip at ~1s on
v5e; the 3-key sort itself is 0.8s): this layout runs 1 sort + the minimal
set of reductions (static need_* flags drop the accumulators a metric set
does not read) instead of 2 sorts + ~10 unconditional reductions. Columns
stay separate [N] arrays: a "fused" [N, k] operand is tile-padded k -> 128
lanes on TPU (a 20x memory blowup measured slower, not faster).

Sampling exactness: the group permutation is uniform iff group hashes are
i.i.d. uniform; the keyed murmur3-style finalizer gives 32-bit avalanche
mixing, and ties (probability ~m^2/2^33 per privacy id with m groups) fall
back to pk order — a negligible, documented bias. Row order within groups
uses an exact uniform tiebreak as before.

All shapes static; caps and clip bounds are runtime scalars. Padding rows
(for sharding) carry valid=False and are routed to the end of the sort.

Pre-sorted ingest (pid_sorted=True): the wire codec delivers rows already
sorted by privacy id within each bucket (ops/wirecodec.py RLE requires it),
so the arrival order IS the primary sort key. The presorted sampler packs
(dense pid-segment index, group_hash, pk, random tiebreak) into THREE
uint32 keys (bit-concatenated, so the 3-key comparison is exactly the
4-field lexicographic order) and carries the value as the only payload —
4 sort operands instead of the general path's 7, and validity becomes
positional (padding is a suffix, so no valid or order operands ride the
sort). Same sampling distribution, cheaper sort: this is the ~2x-headroom
item of BASELINE.md's round-4 floor analysis.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

_INT32_MAX = jnp.iinfo(jnp.int32).max


class PartitionAccumulators(NamedTuple):
    """Per-partition accumulators, each of shape [num_partitions]."""
    pid_count: jnp.ndarray  # distinct privacy units contributing
    count: jnp.ndarray  # kept contributions
    sum: jnp.ndarray  # clipped sum
    norm_sum: jnp.ndarray  # sum of (clip(v) - middle)
    norm_sq_sum: jnp.ndarray  # sum of (clip(v) - middle)^2


def _segment_rank(sorted_is_start: jnp.ndarray) -> jnp.ndarray:
    """Rank of each element within its (contiguous) segment."""
    n = sorted_is_start.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    seg_start = jax.lax.cummax(jnp.where(sorted_is_start, idx, 0))
    return idx - seg_start


class SampledRows(NamedTuple):
    """The Linf/L0 sampling decisions, in (pid, ghash, pk, uniform)-sorted
    order.

    The single source of truth for contribution bounding: every kernel
    (scalar, vector, row-mask) derives from this so their sampling stays
    bit-identical for the same PRNG key.
    """
    order: Optional[jnp.ndarray]  # row permutation (None when not needed)
    spid: jnp.ndarray  # sorted pid keys (padding -> INT32_MAX)
    spk: jnp.ndarray  # sorted pk keys (padding -> INT32_MAX)
    svalid: jnp.ndarray  # sorted validity
    is_start: jnp.ndarray  # (pid, pk)-group start marker
    group_id: jnp.ndarray  # dense (pid, pk)-group index per sorted row
    keep_row: jnp.ndarray  # Linf sampling decision per sorted row
    keep_group_row: jnp.ndarray  # L0 decision of the row's group, per row
    sval: Optional[jnp.ndarray]  # sorted values (when passed to the sort)


def _group_hash(pid: jnp.ndarray, pk: jnp.ndarray,
                salt: jnp.ndarray) -> jnp.ndarray:
    """Keyed 32-bit mix of (pid, pk): the random group order within each
    privacy id (murmur3-style finalizer for avalanche; salt from the PRNG
    key so sampling differs between kernel invocations)."""
    x = pid.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    x = x ^ (pk.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)) ^ salt
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _l1_sample_mask(key: jax.Array, pid: jnp.ndarray, valid: jnp.ndarray,
                    l1_cap) -> jnp.ndarray:
    """Keeps a uniform sample of at most l1_cap rows per privacy id.

    Exact replication of the reference's per-privacy-id L1 bounding
    (SamplingPerPrivacyIdContributionBounder,
    contribution_bounders.py:114-156): sort rows by (pid, uniform) — each
    privacy id's rows land in random order — and keep rank < l1_cap.
    """
    n = pid.shape[0]
    pid_key = jnp.where(valid, pid, _INT32_MAX)
    tiebreak = jax.random.uniform(key, (n,))
    order = jnp.lexsort((tiebreak, pid_key))
    spid = pid_key[order]
    is_start = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), spid[1:] != spid[:-1]])
    keep_sorted = valid[order] & (_segment_rank(is_start) < l1_cap)
    return jnp.zeros((n,), dtype=bool).at[order].set(keep_sorted)


def _sample_rows_and_groups(key: jax.Array, pid: jnp.ndarray,
                            pk: jnp.ndarray, valid: jnp.ndarray, linf_cap,
                            l0_cap, l1_cap=None,
                            value: Optional[jnp.ndarray] = None,
                            need_order: bool = True) -> SampledRows:
    """ONE sort of rows by (pid, group_hash, pk, uniform); samples Linf
    rows and L0 groups from it (module docstring steps 1-3).

    The uniform tiebreak makes each (pid, pk) group a random permutation,
    so "rank < cap" is exact sampling without replacement; the keyed group
    hash makes the groups of each privacy id a random permutation, so
    "group rank within pid < cap" is the cross-partition sample — the
    reference's two sample_fixed_per_key passes from a single sort.

    l1_cap (max_contributions mode): when given, a uniform sample of at
    most l1_cap rows per privacy id is taken FIRST — the total-contribution
    bound whose L1 sensitivity the noise is calibrated to. Passing
    linf/l0 caps >= the data bounds alongside reproduces the reference's
    L1-only bounding exactly.
    """
    n = pid.shape[0]
    k1, k2 = jax.random.split(key)
    if l1_cap is not None:
        valid = _l1_sample_mask(jax.random.fold_in(key, 3), pid, valid,
                                l1_cap)

    # Padding rows sort to the very end (pid is the primary key).
    pid_key = jnp.where(valid, pid, _INT32_MAX)
    pk_key = jnp.where(valid, pk, _INT32_MAX)
    salt = jax.random.bits(k2, (), dtype=jnp.uint32)
    ghash = _group_hash(pid_key, pk_key, salt)

    tiebreak = jax.random.uniform(k1, (n,))
    # One variadic sort carries every payload along: on TPU the sort moves
    # data far cheaper than post-hoc random-access gathers (a single 100M
    # gather costs more than the whole 4-key sort). The order payload rides
    # only for callers that map decisions back to input order (row-mask,
    # vector gather) — the scalar aggregation never reads it, and dropping
    # the operand cannot change the permutation (is_stable fixes tie
    # resolution from the keys alone).
    operands = [pid_key, ghash, pk_key, tiebreak, valid]
    if need_order:
        operands.append(jnp.arange(n, dtype=jnp.int32))
    if value is not None:
        operands.append(value)
    # is_stable: float32 tiebreak collisions must resolve identically in
    # every kernel sharing a PRNG key (bound_row_mask sorts one operand
    # fewer than bound_and_aggregate; an unstable sort could order tied
    # rows differently between the two programs, breaking the replayed
    # sampling guarantee).
    sorted_ops = jax.lax.sort(operands, num_keys=4, is_stable=True)
    spid, sgh, spk, _, svalid = sorted_ops[:5]
    order = sorted_ops[5] if need_order else None
    sval = sorted_ops[-1] if value is not None else None
    is_start = jnp.concatenate([
        jnp.ones((1,), dtype=bool),
        (spid[1:] != spid[:-1]) | (sgh[1:] != sgh[:-1]) |
        (spk[1:] != spk[:-1])
    ])
    keep_row = svalid & (_segment_rank(is_start) < linf_cap)
    group_id = (jnp.cumsum(is_start) - 1).astype(jnp.int32)

    # -- L0 sampling: rank of the row's group within its pid --------------
    # group_id is nondecreasing, so a cummax over the pid-start markers
    # yields the pid's first group id without a gather.
    is_pid_start = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), spid[1:] != spid[:-1]])
    first_group_of_pid = jax.lax.cummax(
        jnp.where(is_pid_start, group_id, 0))
    group_rank = group_id - first_group_of_pid
    keep_group_row = svalid & (group_rank < l0_cap)
    return SampledRows(order, spid, spk, svalid, is_start, group_id,
                       keep_row, keep_group_row, sval)


# -- presorted-pid fast path -------------------------------------------------
#
# Minimum random tiebreak bits for the packed-key sort. Ties fall back to
# stable (arrival) order like the general path's float32 tiebreak ties; 8
# bits would make ties common, so below this the presorted path refuses and
# the caller falls back to the general 4-key sort.
_MIN_RAND_BITS = 12
_KEY_BITS = 96  # three uint32 sort keys

# Executed chunk launches per sampler, one event per launch, counted by
# the streaming drivers (the jitted kernels cannot count per execution):
#   ops/sampler_chunks/<kind>  kind is sampler_kind() of the launched
#                              program, after any hash demotion
# Hash-binned (sortless) group-stage counters, per EXECUTED chunk:
#   ops/hash_bin_occupancy_pct      cumulative per-chunk grid occupancy in
#                                    percent (divide by the hash chunks
#                                    for the mean)
#   ops/hash_bin_overflow_demotions chunks whose RLE entry count exceeded
#                                    the static bin count and were demoted
#                                    to the tiled sort by the host driver
EVENT_HASH_OCCUPANCY = "ops/hash_bin_occupancy_pct"
EVENT_HASH_DEMOTIONS = "ops/hash_bin_overflow_demotions"


def packed_key_layout(n: int, num_partitions: int,
                      max_segments: Optional[int] = None
                      ) -> Tuple[int, int, int, int]:
    """(segbits, pkbits, randbits, padbits) of the packed 3-key layout.

    The single source of truth shared by ``presorted_fits`` and the
    packed/tiled samplers — they previously duplicated these formulas, so
    a drift in one silently broke the fit check at the capacity edge.
    randbits/padbits are only meaningful when the layout fits
    (``presorted_fits``); randbits is clamped to [0, 32].
    """
    seg_cap = int(max_segments) if max_segments is not None else int(n)
    segbits = max(1, seg_cap.bit_length())
    pkbits = max(1, int(max(num_partitions - 1, 0)).bit_length())
    randbits = min(32, max(0, _KEY_BITS - segbits - 32 - pkbits))
    padbits = max(0, _KEY_BITS - segbits - 32 - pkbits - randbits)
    return segbits, pkbits, randbits, padbits


def presorted_fits(n: int, num_partitions: int,
                   max_segments: Optional[int] = None) -> bool:
    """Whether the packed 3-key presorted sort has enough bits for the
    (segment, ghash, pk, rand) fields at this shape."""
    segbits, pkbits, randbits, _ = packed_key_layout(n, num_partitions,
                                                     max_segments)
    return randbits >= _MIN_RAND_BITS


def sampler_kind(n: int, *, num_partitions: int,
                 max_segments: Optional[int], pid_sorted: bool,
                 l1_mode: bool, tile_rows: int, tile_slack: int,
                 hash_bins: int, hash_bin_rows: int) -> str:
    """The sampler a bounding kernel runs at these statics: "empty"
    (n <= 0), "hash", "tiled", "packed" or "general".

    The one place the choice is made: _dispatch_sampler switches on it
    at trace time, and the host drivers and the serving bound-cache key
    (streaming.resolved_sampler_desc) read it. n is the chunk's row
    capacity (the traced ``pid.shape[0]``).
    """
    if n <= 0:
        return "empty"
    if (pid_sorted and not l1_mode
            and presorted_fits(n, num_partitions, max_segments)):
        if hash_bins and hash_bin_rows:
            return "hash"
        if tile_rows and tile_rows + tile_slack < n:
            return "tiled"
        return "packed"
    return "general"


def sampler_event(kind: str) -> str:
    """Name of the executed-launch counter of one sampler kind."""
    return f"ops/sampler_chunks/{kind}"


def _pack_key_bits(fields) -> list:
    """Concatenates (uint32 array, nbits) fields MSB-first into uint32 keys.

    Lexicographic comparison of the returned key list equals lexicographic
    comparison of the field tuple (bit concatenation preserves order).
    Total bits must not exceed _KEY_BITS; a trailing partial key is
    left-aligned (zero-padded on the right, same order).
    """
    keys = []
    acc = None
    filled = 0
    for arr, nbits in fields:
        arr = arr.astype(jnp.uint32)
        remaining = nbits
        while remaining > 0:
            if acc is None:
                acc = jnp.zeros(arr.shape, dtype=jnp.uint32)
                filled = 0
            take = min(32 - filled, remaining)
            part = (arr >> jnp.uint32(remaining - take)) & jnp.uint32(
                (1 << take) - 1)
            acc = (acc << jnp.uint32(take)) | part if filled else part
            filled += take
            remaining -= take
            if filled == 32:
                keys.append(acc)
                acc = None
    if acc is not None:
        keys.append(acc << jnp.uint32(32 - filled))
    return keys


def _extract_key_bits(keys, start: int, nbits: int) -> jnp.ndarray:
    """Reads bit field [start, start+nbits) back out of packed keys.

    Bit 0 is the MSB of keys[0] (the packing order of _pack_key_bits).
    nbits must be < 32.
    """
    out = None
    end = start + nbits
    for i, kk in enumerate(keys):
        k_lo, k_hi = 32 * i, 32 * i + 32
        lo, hi = max(start, k_lo), min(end, k_hi)
        if lo >= hi:
            continue
        part = (kk >> jnp.uint32(k_hi - hi)) & jnp.uint32(
            (1 << (hi - lo)) - 1)
        out = part if out is None else (out << jnp.uint32(hi - lo)) | part
    return out


def _prefix_changed(keys, prefix_bits: int) -> jnp.ndarray:
    """bool[n]: row's first prefix_bits differ from the previous row's
    (row 0 -> True). Used to find group/pid boundaries in packed-key
    sorted order without re-deriving the fields."""
    changed = None
    remaining = prefix_bits
    for kk in keys:
        if remaining <= 0:
            break
        if remaining >= 32:
            part = kk
        else:
            part = kk >> jnp.uint32(32 - remaining)
        c = part[1:] != part[:-1]
        changed = c if changed is None else (changed | c)
        remaining -= 32
    return jnp.concatenate([jnp.ones((1,), dtype=bool), changed])


def _sampler_randomness(key: jax.Array, n: int, randbits: int):
    """(salt, rand): the PRNG draws of the presorted samplers.

    Shared by the packed/tiled sort-key construction AND the hash-binned
    stage — draw-for-draw the same derivation (salt from the second split,
    per-row tiebreak bits from the first, truncated to the packed layout's
    rand field), so every sampler keyed the same way makes identical
    sampling decisions."""
    k1, k2 = jax.random.split(key)
    salt = jax.random.bits(k2, (), dtype=jnp.uint32)
    rand = jax.random.bits(k1, (n,), dtype=jnp.uint32)
    if randbits < 32:
        rand = rand >> jnp.uint32(32 - randbits)
    return salt, rand


def _packed_sort_fields(key: jax.Array, pid: jnp.ndarray, pk: jnp.ndarray,
                        valid: jnp.ndarray, *, num_partitions: int,
                        max_segments: int):
    """Shared key construction of the packed and tiled presorted samplers.

    Returns (keys, is_new_pid, segbits, pkbits): the three uint32 sort
    keys (padding rows already forced to all-ones, sorting strictly last)
    and the pid-boundary mask the tiled path bins from. Both samplers MUST
    derive their keys here — the tiled path's bit-parity contract is that
    its key sequence (and therefore every downstream sampling decision) is
    identical to the packed global sort's.
    """
    n = pid.shape[0]
    segbits, pkbits, randbits, padbits = packed_key_layout(
        n, num_partitions, max_segments)
    salt, rand = _sampler_randomness(key, n, randbits)
    ghash = _group_hash(pid, pk, salt)

    is_new_pid = valid & jnp.concatenate(
        [jnp.ones((1,), dtype=bool), pid[1:] != pid[:-1]])
    seg = jnp.maximum(jnp.cumsum(is_new_pid.astype(jnp.int32)) - 1,
                      0).astype(jnp.uint32)
    fields = [(seg, segbits), (ghash, 32),
              (pk.astype(jnp.uint32), pkbits), (rand, randbits)]
    if padbits:
        fields.append((jnp.zeros((n,), dtype=jnp.uint32), padbits))
    keys = _pack_key_bits(fields)
    # Padding rows sort strictly last: all-ones keys, and a valid row's
    # segment field is <= max_segments - 1 < 2^segbits - 1.
    ones = jnp.uint32(0xFFFFFFFF)
    keys = [jnp.where(valid, kk, ones) for kk in keys]
    return keys, is_new_pid, segbits, pkbits


def _sampled_from_packed(skeys, n: int, n_valid, segbits: int, pkbits: int,
                         linf_cap, l0_cap, sval, order) -> SampledRows:
    """Shared epilogue over packed-key-sorted rows: field extraction,
    segment/group boundaries, Linf/L0 sampling. Validity is positional
    (padding keys are all-ones, strictly above any valid key)."""
    with jax.named_scope("sample"):
        svalid = jnp.arange(n, dtype=jnp.int32) < n_valid
        sseg = _extract_key_bits(skeys, 0, segbits).astype(jnp.int32)
        spk = _extract_key_bits(skeys, segbits + 32, pkbits).astype(jnp.int32)

        is_start = _prefix_changed(skeys, segbits + 32 + pkbits)
        keep_row = svalid & (_segment_rank(is_start) < linf_cap)
        group_id = (jnp.cumsum(is_start) - 1).astype(jnp.int32)
        is_pid_start = _prefix_changed(skeys, segbits)
        first_group_of_pid = jax.lax.cummax(
            jnp.where(is_pid_start, group_id, 0))
        group_rank = group_id - first_group_of_pid
        keep_group_row = svalid & (group_rank < l0_cap)
        return SampledRows(order, sseg, spk, svalid, is_start, group_id,
                           keep_row, keep_group_row, sval)


def _sample_rows_and_groups_presorted(key: jax.Array, pid: jnp.ndarray,
                                      pk: jnp.ndarray, valid: jnp.ndarray,
                                      linf_cap, l0_cap, *,
                                      num_partitions: int,
                                      max_segments: int,
                                      value: Optional[jnp.ndarray] = None,
                                      need_order: bool = False
                                      ) -> SampledRows:
    """The presorted-ingest twin of _sample_rows_and_groups.

    Contract (guaranteed structurally by wirecodec.decode_bucket):
      * valid is a prefix mask (valid == iota < n_valid);
      * pid is nondecreasing over the valid prefix;
      * the number of distinct pids among valid rows is <= max_segments.

    Because arrival order is already pid-major, the privacy id never rides
    the sort: rows get a dense pid-segment index (one cumsum), and
    (segment, group_hash, pk, random tiebreak) are bit-packed into three
    uint32 keys whose 3-key lexicographic comparison equals the general
    path's 4-field order. The value is the only payload, so the sort moves
    4 operands instead of 7. Validity is positional after the sort
    (padding keys are all-ones, strictly above any valid key), and ghash
    collisions resolve exactly like the general path: equal (seg, ghash)
    keys compare by the pk field, then the tiebreak, then stable order.

    Returned SampledRows: spid holds the segment index (the kernels only
    use pid equality structure); order is None unless need_order.
    """
    n = pid.shape[0]
    keys, _, segbits, pkbits = _packed_sort_fields(
        key, pid, pk, valid, num_partitions=num_partitions,
        max_segments=max_segments)

    operands = list(keys)
    if value is not None:
        operands.append(value)
    if need_order:
        operands.append(jnp.arange(n, dtype=jnp.int32))
    sorted_ops = jax.lax.sort(operands, num_keys=3, is_stable=True)
    skeys = sorted_ops[:3]
    sval = sorted_ops[3] if value is not None else None
    order = sorted_ops[-1] if need_order else None

    n_valid = jnp.sum(valid.astype(jnp.int32))
    return _sampled_from_packed(skeys, n, n_valid, segbits, pkbits,
                                linf_cap, l0_cap, sval, order)


def _sample_rows_and_groups_tiled(key: jax.Array, pid: jnp.ndarray,
                                  pk: jnp.ndarray, valid: jnp.ndarray,
                                  linf_cap, l0_cap, *,
                                  num_partitions: int,
                                  max_segments: int,
                                  tile_rows: int,
                                  tile_slack: int,
                                  value: Optional[jnp.ndarray] = None,
                                  need_order: bool = False
                                  ) -> SampledRows:
    """Bucketed segment-local twin of _sample_rows_and_groups_presorted.

    Same contract, same packed keys, BIT-IDENTICAL sampling decisions —
    but the sort runs over fixed-width tiles instead of the whole chunk,
    dropping sort cost from O(n log n) to O(n log B):

      1. one-pass hash-bucket binning: each row's pid-segment START index
         (a cummax over the pid boundaries, the same machinery as
         _segment_rank) assigns the whole segment to tile
         ``start // tile_rows`` — so no segment ever straddles a tile and
         tile t's segments all precede tile t+1's;
      2. rows gather into a [n_tiles, tile_rows + tile_slack] grid at slot
         ``row - tile * tile_rows`` (injective; slack absorbs a segment
         that begins near a tile's end — the caller guarantees no pid has
         more than tile_slack rows, derived from the wire's prep-time
         per-pid run counts), empty slots carrying all-ones keys;
      3. ONE batched stable 3-key sort along the tile axis — slots are in
         arrival order within each tile, so stable per-tile ties resolve
         exactly like the global stable sort's;
      4. tiles compact back to [n] by concatenating their valid prefixes
         (per-tile valid counts -> offsets -> a near-sequential gather).

    Equal keys never span tiles (equal seg => same segment => same tile),
    and segments are tile-ordered, so the concatenation IS the globally
    sorted sequence: identical keys, identical tie order, therefore
    identical SampledRows bits to the packed global sort.

    Contract violation backstop: if a segment exceeds tile_slack rows
    (corrupt wire metadata — the drivers' prep-count guard fires first on
    the native path), overflowing rows drop from the grid; the binned-row
    count then disagrees with n_valid and every row is invalidated, so a
    violated contract yields empty accumulators rather than a silently
    re-sampled release.
    """
    n = pid.shape[0]
    keys, is_new_pid, segbits, pkbits = _packed_sort_fields(
        key, pid, pk, valid, num_partitions=num_partitions,
        max_segments=max_segments)

    b = int(tile_rows)
    w = int(tile_rows + tile_slack)
    t = -(-n // b)
    idx = jnp.arange(n, dtype=jnp.int32)
    seg_start = jax.lax.cummax(jnp.where(is_new_pid, idx, 0))
    tile_of = seg_start // jnp.int32(b)

    # Grid gather: candidate source row of slot (tile, j) is
    # tile * tile_rows + j; it belongs there iff its segment starts in
    # this tile. Near-sequential reads (each row is probed by at most two
    # tiles), no scatter.
    src = (jnp.arange(t, dtype=jnp.int32)[:, None] * b
           + jnp.arange(w, dtype=jnp.int32)[None, :])
    srcc = jnp.minimum(src, n - 1)
    slot_valid = ((src < n) & valid[srcc]
                  & (tile_of[srcc]
                     == jnp.arange(t, dtype=jnp.int32)[:, None]))
    ones = jnp.uint32(0xFFFFFFFF)
    operands = [jnp.where(slot_valid, kk[srcc], ones) for kk in keys]
    if value is not None:
        operands.append(
            jnp.where(slot_valid, value[srcc],
                      jnp.zeros((), dtype=value.dtype)))
    if need_order:
        operands.append(jnp.where(slot_valid, srcc, n - 1))
    sorted_ops = jax.lax.sort(operands, dimension=1, num_keys=3,
                              is_stable=True)

    # Compaction: tile t's valid rows sorted to its prefix of length m[t];
    # output row i lives at (tile t*, slot i - offset[t*]).
    m = jnp.sum(slot_valid.astype(jnp.int32), axis=1)
    cum = jnp.cumsum(m)
    t_star = jnp.minimum(
        jnp.searchsorted(cum, idx, side="right").astype(jnp.int32), t - 1)
    j_star = idx - (cum[t_star] - m[t_star])
    flat = jnp.clip(t_star * w + j_star, 0, t * w - 1)

    n_valid = jnp.sum(valid.astype(jnp.int32))
    # Contract backstop (docstring): dropped rows invalidate everything.
    n_valid = jnp.where(cum[-1] == n_valid, n_valid, 0)
    tail = idx >= n_valid
    skeys = [jnp.where(tail, ones, op.reshape(-1)[flat])
             for op in sorted_ops[:3]]
    pos = 3
    sval = None
    if value is not None:
        sval = jnp.where(tail, jnp.zeros((), dtype=value.dtype),
                         sorted_ops[3].reshape(-1)[flat])
        pos = 4
    order = None
    if need_order:
        # Tail rows point at themselves: under the prefix-validity
        # contract those are exactly the padding input rows, so the
        # scatter-back in bound_row_mask never collides with a valid row.
        order = jnp.where(tail, idx, sorted_ops[pos].reshape(-1)[flat])
    return _sampled_from_packed(skeys, n, n_valid, segbits, pkbits,
                                linf_cap, l0_cap, sval, order)


class BinnedRows(NamedTuple):
    """The hash-binned (sortless) twin of SampledRows
    (``segment_sort="hash"``).

    Rows never ride a sort: each pid segment occupies one row of a
    ``[hash_bins, hash_bin_rows]`` grid (cells in arrival order), and
    the Linf/L0 sampling decisions come from keyed-priority selection
    inside each bin — pairwise comparisons against the SAME salt /
    truncated-rand draws the packed sort uses as its keys
    (``_sampler_randomness``), so the sampled row multiset is identical
    to the sort path's prefix-take for the same PRNG key.

    Row-domain fields (original arrival order, [n]): keep_row /
    keep_group_row are the Linf / L0 decisions; lead_row marks each
    KEPT group's leader (its first row in arrival order) — the slot the
    group's accumulator columns live at.

    Grid-domain fields ([hash_bins, hash_bin_rows] or [.., .., W]):
    trace-time context for the group reduce — ``same`` is the
    group-membership pairwise mask, ``contrib`` additionally gates the
    contributor by its Linf decision, ``cell`` maps each row to its
    flat grid cell, ``sval`` is the value gathered into the grid.

    ``ok`` is the contract backstop: False (a row failed to bin — the
    per-segment width contract was violated by corrupt wire metadata)
    empties every decision, so a violated contract yields empty
    accumulators rather than a silently re-sampled release, exactly
    like the tiled sampler's slack backstop.
    """
    keep_row: jnp.ndarray  # [n] Linf decision per row
    keep_group_row: jnp.ndarray  # [n] L0 decision of the row's group
    lead_row: jnp.ndarray  # [n] kept-group leader marker
    cell: jnp.ndarray  # [n] flat grid cell of each row
    same: jnp.ndarray  # [S, W, W] same-group pairwise mask
    contrib: jnp.ndarray  # [S, W, W] same-group & contributor-kept
    grid_valid: jnp.ndarray  # [S, W] occupied-cell mask
    spk: jnp.ndarray  # [S, W] partition ids on the grid
    sval: Optional[jnp.ndarray]  # [S, W] value on the grid
    ok: jnp.ndarray  # scalar backstop


def _bin_rows_and_groups_hash(key: jax.Array, pid: jnp.ndarray,
                              pk: jnp.ndarray, valid: jnp.ndarray,
                              linf_cap, l0_cap, *, num_partitions: int,
                              max_segments: int, hash_bins: int,
                              hash_bin_rows: int,
                              value: Optional[jnp.ndarray] = None
                              ) -> BinnedRows:
    """The sortless group-stage sampler: one scatter into per-segment
    bins, keyed-priority selection inside each bin, ZERO sort passes.

    Same presorted-ingest contract as the packed/tiled samplers (valid
    prefix, pid nondecreasing, distinct pids <= max_segments) plus the
    host-sized grid geometry: hash_bins >= the chunk's pid segments
    (the driver demotes chunks that do not fit — n_uniq > hash_bins —
    to the tiled kernel) and hash_bin_rows >= the longest single-pid
    run (row_packer prep stats, like tile_slack).

    Sampling-parity argument (the load-bearing contract): the packed
    sort orders rows by (segment, ghash, pk, rand, arrival) and takes
    per-group / per-segment prefixes. Here every decision is the rank
    form of the same order — a row's Linf rank is the count of
    same-group rows with smaller (rand, arrival), a group's L0 rank is
    the count of distinct same-segment groups with smaller (ghash, pk)
    — over the identical salt/rand draws (_sampler_randomness). The
    kept row multiset and kept group set are therefore IDENTICAL to the
    sort path's for the same key; only the accumulation order differs
    (which the int-exactness gate makes bit-invisible).
    """
    n = pid.shape[0]
    s_bins = int(hash_bins)
    w = int(hash_bin_rows)
    _, _, randbits, _ = packed_key_layout(n, num_partitions, max_segments)
    salt, rand = _sampler_randomness(key, n, randbits)
    ghash = _group_hash(pid, pk, salt)

    idx = jnp.arange(n, dtype=jnp.int32)
    is_new_pid = valid & jnp.concatenate(
        [jnp.ones((1,), dtype=bool), pid[1:] != pid[:-1]])
    seg = jnp.maximum(jnp.cumsum(is_new_pid.astype(jnp.int32)) - 1, 0)
    seg_start = jax.lax.cummax(jnp.where(is_new_pid, idx, 0))

    # Bin scatter: segment s's rows land in grid row s at their
    # within-segment position (injective; segments are arrival-
    # contiguous so the grid gather below is near-sequential). Segments
    # beyond hash_bins drop and trip the ok backstop.
    starts = jnp.zeros((s_bins,), jnp.int32).at[seg].max(seg_start,
                                                         mode="drop")
    src = starts[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    srcc = jnp.minimum(src, n - 1)
    grid_valid = ((src < n) & valid[srcc]
                  & (seg[srcc]
                     == jnp.arange(s_bins, dtype=jnp.int32)[:, None]))
    n_valid = jnp.sum(valid.astype(jnp.int32))
    ok = jnp.sum(grid_valid.astype(jnp.int32)) == n_valid

    ones32 = jnp.uint32(0xFFFFFFFF)
    bg = jnp.where(grid_valid, ghash[srcc], ones32)
    bpk = jnp.where(grid_valid, pk[srcc], _INT32_MAX).astype(jnp.int32)
    brand = jnp.where(grid_valid, rand[srcc], ones32)
    sval = None
    if value is not None:
        sval = jnp.where(grid_valid, value[srcc],
                         jnp.zeros((), dtype=value.dtype))

    # Pairwise keyed-priority selection, j = target cell, k = the other
    # cell of the same bin (XLA fuses each mask chain into its reduce —
    # nothing [S, W, W]-sized is materialized).
    cv_j = grid_valid[:, :, None]
    cv_k = grid_valid[:, None, :]
    tri = (jnp.arange(w, dtype=jnp.int32)[None, :, None]
           > jnp.arange(w, dtype=jnp.int32)[None, None, :])  # k before j
    same = (cv_j & cv_k
            & (bg[:, :, None] == bg[:, None, :])
            & (bpk[:, :, None] == bpk[:, None, :]))
    # Linf: rank within the group by (rand, arrival) — the packed
    # sort's tiebreak key and its stable tie order.
    before_in_group = same & ((brand[:, None, :] < brand[:, :, None])
                              | ((brand[:, None, :] == brand[:, :, None])
                                 & tri))
    keep_row_grid = grid_valid & (jnp.sum(before_in_group, axis=2)
                                  < linf_cap)
    is_leader = grid_valid & ~jnp.any(same & tri, axis=2)
    # L0: rank of the row's group among the segment's distinct groups
    # ordered by (ghash, pk) — count the leaders with a smaller key
    # (evaluated at every member: its key equals its leader's).
    gkey_lt = (is_leader[:, None, :] & cv_j
               & ((bg[:, None, :] < bg[:, :, None])
                  | ((bg[:, None, :] == bg[:, :, None])
                     & (bpk[:, None, :] < bpk[:, :, None]))))
    keep_group_grid = grid_valid & (jnp.sum(gkey_lt, axis=2) < l0_cap)
    contrib = same & keep_row_grid[:, None, :]

    # Gather the decisions back to the (smaller) row domain: each valid
    # row's cell is (seg, idx - seg_start); the backstop masks
    # everything when any row failed to bin.
    cell = jnp.clip(seg * w + (idx - seg_start), 0, s_bins * w - 1)
    rv = valid & ok
    keep_row = rv & keep_row_grid.reshape(-1)[cell]
    keep_group_row = rv & keep_group_grid.reshape(-1)[cell]
    lead_row = (rv & is_leader.reshape(-1)[cell]
                & keep_group_grid.reshape(-1)[cell])
    return BinnedRows(keep_row, keep_group_row, lead_row, cell, same,
                      contrib, grid_valid, bpk, sval, ok)


def _hash_group_sum(b: BinnedRows, col: jnp.ndarray) -> jnp.ndarray:
    """[n] per-group sum of a grid column over the group's KEPT rows,
    gathered back to each row (meaningful at lead_row slots). One fused
    mask-multiply-reduce over the bins — the sortless replacement for
    the sorted path's group segment-sum."""
    g = jnp.sum(jnp.where(b.contrib, col[:, None, :],
                          jnp.zeros((), dtype=col.dtype)), axis=2)
    return g.reshape(-1)[b.cell]


def _dispatch_sampler(key, pid, pk, valid, linf_cap, l0_cap, l1_cap, *,
                      num_partitions, max_segments, pid_sorted, tile_rows,
                      tile_slack, value, need_order=False,
                      hash_bins=0, hash_bin_rows=0):
    """Trace-time sampler dispatch shared by every bounding kernel.

    pid_sorted/max_segments/tile_*/hash_* are static and `l1_cap is
    None` is a pytree-structure (not value) test — the branch is
    deliberately resolved at trace time, like the need_* flags. All
    samplers produce the same sampling distribution; the packed, tiled
    and hash-binned presorted samplers additionally make BIT-identical
    sampling decisions (the hash path returns them as a
    :class:`BinnedRows` rank view instead of a sorted sequence —
    callers branch on the type at trace time).

    The choice is sampler_kind's (hash-binned, tiled, packed global or
    general 4-key sort); this function only switches on it.
    """
    n = pid.shape[0]
    kind = sampler_kind(
        n, num_partitions=num_partitions, max_segments=max_segments,
        pid_sorted=pid_sorted, l1_mode=l1_cap is not None,
        tile_rows=tile_rows, tile_slack=tile_slack, hash_bins=hash_bins,
        hash_bin_rows=hash_bin_rows)
    max_seg = int(max_segments) if max_segments else n
    # dplint: disable=DPL003 — static/structural branch, resolved per compile
    if kind == "hash":
        return _bin_rows_and_groups_hash(
            key, pid, pk, valid, linf_cap, l0_cap,
            num_partitions=num_partitions, max_segments=max_seg,
            hash_bins=hash_bins, hash_bin_rows=hash_bin_rows, value=value)
    if kind == "tiled":
        return _sample_rows_and_groups_tiled(
            key, pid, pk, valid, linf_cap, l0_cap,
            num_partitions=num_partitions, max_segments=max_seg,
            tile_rows=tile_rows, tile_slack=tile_slack, value=value,
            need_order=need_order)
    if kind == "packed":
        return _sample_rows_and_groups_presorted(
            key, pid, pk, valid, linf_cap, l0_cap,
            num_partitions=num_partitions, max_segments=max_seg,
            value=value, need_order=need_order)
    return _sample_rows_and_groups(key, pid, pk, valid, linf_cap, l0_cap,
                                   l1_cap, value=value,
                                   need_order=need_order)


def _narrow_sort_value(value, value_is_index: bool, value_sort_bits: int):
    """Value operand as it rides the sort: index payloads narrow to the
    smallest dtype their plane count fits (uint8/uint16), halving or
    quartering the payload bytes the sort moves."""
    if not value_is_index or value is None or not value_sort_bits:
        return value
    if value_sort_bits <= 8:
        return value.astype(jnp.uint8)
    if value_sort_bits <= 16:
        return value.astype(jnp.uint16)
    return value


def _widen_sorted_value(sval, value_is_index: bool, value_lo, value_scale):
    """(float value column, int32 index column or None) post-sort.

    The float expression mirrors wirecodec.decode_bucket's plane
    reconstruction bit for bit, so moving the widening to after the sort
    cannot change any released value.
    """
    if not value_is_index:
        return sval, None
    sval_i = sval.astype(jnp.int32)
    sval_f = (jnp.float32(value_lo)
              + sval_i.astype(jnp.float32) * jnp.float32(value_scale))
    return sval_f, sval_i


def _int_plan_bounds(plan_lo, plan_scale, plan_bits: int, row_clip_lo,
                     row_clip_hi, linf_cap
                     ) -> Optional[Tuple[int, int, float]]:
    """(int clip lo, int clip hi, max |clipped row value|) under the
    int-exactness gate, or None — the shared core of
    int_accumulation_plan and hash_exact_gate."""
    try:
        linf = int(linf_cap)
    except (TypeError, ValueError):
        return None
    lo, scale = float(plan_lo), float(plan_scale)
    if linf < 1 or not lo.is_integer() or not scale.is_integer():
        return None
    max_idx = (1 << int(plan_bits)) - 1
    if abs(lo) + max_idx * abs(scale) >= (1 << 24):
        return None
    bounds = [abs(lo), abs(lo + max_idx * scale)]
    iclo, ichi = -(2**31) + 1, 2**31 - 1
    for bound, is_lo in ((float(row_clip_lo), True),
                        (float(row_clip_hi), False)):
        if math.isfinite(bound):
            if not bound.is_integer():
                return None
            bounds.append(abs(bound))
            if is_lo:
                iclo = int(bound)
            else:
                ichi = int(bound)
        elif math.isnan(bound):
            return None
    if linf * max(bounds) >= (1 << 24):
        return None
    return iclo, ichi, float(max(bounds))


def int_accumulation_plan(plan_lo, plan_scale, plan_bits: int, row_clip_lo,
                          row_clip_hi, linf_cap
                          ) -> Optional[Tuple[int, int]]:
    """(int-domain row clip bounds) when the group-stage count and sum
    columns may accumulate in int32 BIT-IDENTICALLY to the float32 path,
    else None.

    Exactness argument: when the value grid (lo + idx * scale) and any
    finite row clip bound are integers, AND |lo| + max_idx * |scale| <
    2^24 (so the float32 reconstruction's intermediate product and sum
    are themselves exactly representable integers — without this a
    product >= 2^24 can round, e.g. lo=-16777215, scale=3, idx=5592407
    reconstructs 5.0 in float32 but 6 in int32), every per-row clipped
    value is the same exact integer in float32 AND int32; with at most
    linf_cap kept rows per group and linf_cap * max|value| < 2^24, every
    float32 partial sum of the legacy group segment-sum is an exactly
    representable integer — so the int32 sums widen to the same float32
    bits at the partition fold. Requires a concrete (host) linf_cap; a
    traced cap cannot be bounded statically.
    """
    r = _int_plan_bounds(plan_lo, plan_scale, plan_bits, row_clip_lo,
                         row_clip_hi, linf_cap)
    return None if r is None else (r[0], r[1])


def hash_exact_gate(plan_lo, plan_scale, plan_bits: int, row_clip_lo,
                    row_clip_hi, linf_cap, group_clip_lo, group_clip_hi,
                    cap_rows) -> bool:
    """Whether the hash-binned group stage is BIT-identical to the
    sorted paths at this configuration, regardless of reduction order.

    Strengthens the int_accumulation_plan gate so that EVERY float32
    partial sum anywhere in the kernel — group stage and partition fold,
    in any association — is an exactly representable integer, making
    the accumulation order (the only thing that differs between the
    hash-binned and sorted group stages; the sampled multiset is
    identical) bit-invisible:

      * the int plan holds (integer grid, integer row clips,
        linf_cap * max|v| < 2^24 — group partials exact);
      * finite group-sum clip bounds are integers (clipped group sums
        stay integers) — a clip can RAISE a magnitude (clip(5, 1000,
        inf) = 1000), so its bounds enter the partition bound below;
      * cap_rows < 2^24 (partition counts / pid-counts exact) and
        cap_rows * max(|v|, |finite group clips|) < 2^24 (partition
        sums exact: at most cap_rows groups, each bounded by the row
        total or its clip).

    The norm columns (mean/variance) are non-integer, so this gate
    only certifies kernels that do not read them — the auto dispatch
    additionally requires need_norm = need_norm_sq = False.
    """
    r = _int_plan_bounds(plan_lo, plan_scale, plan_bits, row_clip_lo,
                         row_clip_hi, linf_cap)
    if r is None:
        return False
    vmax = r[2]
    bound = vmax
    for b in (group_clip_lo, group_clip_hi):
        fb = float(b)
        if math.isnan(fb):
            return False
        if math.isfinite(fb):
            if not fb.is_integer():
                return False
            bound = max(bound, abs(fb))
    try:
        cap = int(cap_rows)
    except (TypeError, ValueError):
        return False
    return cap < (1 << 24) and cap * bound < (1 << 24)


def _hash_partition_accumulators(s: BinnedRows, pk: jnp.ndarray, *,
                                 num_partitions: int, row_clip_lo,
                                 row_clip_hi, middle, group_clip_lo,
                                 group_clip_hi, need_count, need_sum,
                                 need_norm, need_norm_sq, has_group_clip,
                                 value_is_index, value_lo, value_scale
                                 ) -> PartitionAccumulators:
    """Partition accumulators straight out of the hash bins: per-group
    sums at leader rows, then ONE stacked scatter covering every
    accumulator column ([num_partitions, n_cols] with a [n, n_cols]
    update set — the "one scatter per accumulator" shape, fused).

    The accumulation order differs from the sorted paths (row order vs
    group-sorted order), which the hash_exact_gate makes bit-invisible;
    outside the gate counts stay exact and sums are ULP-close.
    """
    sval, _ = _widen_sorted_value(s.sval, value_is_index, value_lo,
                                  value_scale)
    dtype = jnp.promote_types(sval.dtype, jnp.float32)
    vclip = jnp.clip(sval, row_clip_lo, row_clip_hi).astype(dtype)
    vnorm = vclip - middle
    gw = s.lead_row.astype(dtype)
    cols = [gw]  # pid_count: one per kept group
    if need_count:
        cols.append(_hash_group_sum(s, jnp.ones_like(vclip)) * gw)
    if need_sum:
        g_sum = _hash_group_sum(s, vclip)
        if has_group_clip:
            g_sum = jnp.clip(g_sum, group_clip_lo, group_clip_hi)
        cols.append(g_sum * gw)
    if need_norm:
        cols.append(_hash_group_sum(s, vnorm) * gw)
    if need_norm_sq:
        cols.append(_hash_group_sum(s, vnorm * vnorm) * gw)

    tgt = jnp.where(s.lead_row, pk, num_partitions).astype(jnp.int32)
    out = jnp.zeros((num_partitions, len(cols)), dtype).at[tgt].add(
        jnp.stack(cols, axis=-1), mode="drop")
    zeros = jnp.zeros((num_partitions,), dtype=dtype)
    slot = iter(range(1, len(cols)))
    return PartitionAccumulators(
        pid_count=out[:, 0],
        count=out[:, next(slot)] if need_count else zeros,
        sum=out[:, next(slot)] if need_sum else zeros,
        norm_sum=out[:, next(slot)] if need_norm else zeros,
        norm_sq_sum=out[:, next(slot)] if need_norm_sq else zeros,
    )


@functools.partial(jax.jit,
                   static_argnames=("num_partitions", "need_count",
                                    "need_sum", "need_norm",
                                    "need_norm_sq", "has_group_clip",
                                    "pid_sorted", "max_segments",
                                    "tile_rows", "tile_slack",
                                    "hash_bins", "hash_bin_rows",
                                    "value_is_index", "value_sort_bits",
                                    "int_accumulate"))
def bound_and_aggregate(key: jax.Array,
                        pid: jnp.ndarray,
                        pk: jnp.ndarray,
                        value: jnp.ndarray,
                        valid: jnp.ndarray,
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
                        need_count: bool = True,
                        need_sum: bool = True,
                        need_norm: bool = True,
                        need_norm_sq: bool = True,
                        has_group_clip: bool = True,
                        pid_sorted: bool = False,
                        max_segments: Optional[int] = None,
                        tile_rows: int = 0,
                        tile_slack: int = 0,
                        hash_bins: int = 0,
                        hash_bin_rows: int = 0,
                        value_is_index: bool = False,
                        value_lo=0.0,
                        value_scale=1.0,
                        value_sort_bits: int = 0,
                        int_accumulate: bool = False,
                        int_clip_lo=None,
                        int_clip_hi=None
                        ) -> PartitionAccumulators:
    """Contribution bounding + per-partition aggregation, fully fused.

    Args:
      key: PRNG key for the sampling tiebreaks.
      pid, pk: int32[N] dense ids; pk in [0, num_partitions).
      value: float32[N].
      valid: bool[N] — False for padding rows.
      num_partitions: static partition-vocabulary size.
      linf_cap: max contributions kept per (pid, pk) — pass N to disable.
      l0_cap: max partitions kept per pid.
      row_clip_lo/hi: per-contribution clip bounds (+-inf to disable).
      middle: normalization midpoint for mean/variance sums.
      group_clip_lo/hi: per-partition-sum clip bounds (+-inf to disable) —
        the min/max_sum_per_partition mode of SumCombiner.
      l1_cap: max_contributions mode — uniform per-privacy-id total sample
        applied before everything else (pass linf/l0 caps >= data bounds).
      pid_sorted: the input satisfies the presorted-ingest contract (pid
        nondecreasing over a valid prefix — see
        _sample_rows_and_groups_presorted); the sampler then runs the
        cheaper packed-3-key sort. Same sampling distribution, different
        draws. Ignored in L1 mode (the L1 pre-sample breaks the
        prefix-validity invariant).
      max_segments: static upper bound on distinct pids among valid rows
        (presorted path only; tightens the packed segment field — the wire
        decode path passes its RLE entry capacity).
      tile_rows/tile_slack: static tile geometry of the bucketed
        segment-local sort (_sample_rows_and_groups_tiled); 0 keeps the
        global packed sort. Requires pid_sorted and tile_slack >= the
        longest single-pid run (the drivers derive it from the wire's
        prep-time per-pid counts). Bit-identical sampling either way.
      hash_bins/hash_bin_rows: static grid geometry of the sortless
        hash-binned group stage (_bin_rows_and_groups_hash;
        segment_sort="hash") — takes precedence over tile geometry.
        Requires pid_sorted, hash_bins >= the chunk's distinct pids and
        hash_bin_rows >= the longest single-pid run (both host-derived
        from the wire's prep stats; the drivers demote chunks that do
        not fit back to the tiled kernel). Identical sampled multiset;
        bit-identical released values under columnar.hash_exact_gate,
        ULP-close sums (exact counts) otherwise. int_accumulate is
        ignored on this path — under the gate its float32 sums are
        already exact integers, which is the same bits.
      value_is_index: the value column arrives as the int32 affine plane
        index of the wire codec (VALUE_PLANES); it rides the sort narrow
        (value_sort_bits picks uint8/uint16 when the plane count fits)
        and widens to float32 AFTER the sort with
        value_lo + idx * value_scale — the exact decode expression, so
        released values are unchanged.
      int_accumulate: accumulate the group-stage count and sum columns in
        int32, widening to float32 only at the partition fold. Only valid
        under the int_accumulation_plan gate (host-verified exactness —
        bit-identical to the float32 path); int_clip_lo/hi are the
        int-domain row clip bounds the plan returned. Ignored without a
        group stage.
    """
    n = pid.shape[0]
    if n == 0:
        # Same dtype contract as the non-empty path, which accumulates in
        # at least float32 regardless of the value dtype.
        zeros = jnp.zeros((num_partitions,), dtype=jnp.float32)
        return PartitionAccumulators(zeros, zeros, zeros, zeros, zeros)
    with jax.named_scope("group_sort"):
        s = _dispatch_sampler(
            key, pid, pk, valid, linf_cap, l0_cap, l1_cap,
            num_partitions=num_partitions, max_segments=max_segments,
            pid_sorted=pid_sorted, tile_rows=tile_rows,
            tile_slack=tile_slack, hash_bins=hash_bins,
            hash_bin_rows=hash_bin_rows,
            value=_narrow_sort_value(value, value_is_index,
                                     value_sort_bits))
    if isinstance(s, BinnedRows):
        # Sortless group stage: per-group sums inside the bins, one
        # stacked scatter straight to the partition accumulators.
        with jax.named_scope("group_reduce"):
            return _hash_partition_accumulators(
                s, pk, num_partitions=num_partitions,
                row_clip_lo=row_clip_lo, row_clip_hi=row_clip_hi,
                middle=middle, group_clip_lo=group_clip_lo,
                group_clip_hi=group_clip_hi, need_count=need_count,
                need_sum=need_sum, need_norm=need_norm,
                need_norm_sq=need_norm_sq, has_group_clip=has_group_clip,
                value_is_index=value_is_index, value_lo=value_lo,
                value_scale=value_scale)
    sval, sval_i = _widen_sorted_value(s.sval, value_is_index, value_lo,
                                       value_scale)

    # -- rows -> (pid, pk) group accumulators ------------------------------
    # Separate scalar segment-sums over the sorted (monotone) group ids:
    # on TPU a narrow [N, k] operand is tile-padded k -> 128 lanes (a 20x
    # memory blowup), so per-column passes with indices_are_sorted=True are
    # the fast layout. The normalized columns are reduced directly (not
    # derived from sum/count algebra) so large-magnitude values keep full
    # float precision — (v - middle) is small even when v is not.
    # Accumulate in at least float32: a float16 value column must not
    # degrade counts, sums, or routing (only individual contributions may
    # carry reduced precision).
    dtype = jnp.promote_types(sval.dtype, jnp.float32)
    w = s.keep_row.astype(dtype)
    vclip = jnp.clip(sval, row_clip_lo, row_clip_hi).astype(dtype)
    vnorm = vclip - middle
    start_w = (s.is_start & s.svalid).astype(dtype)
    zeros = jnp.zeros((num_partitions,), dtype=dtype)
    if not has_group_clip:
        # No per-(pid, pk) group clipping: every accumulator is additive
        # over rows, so rows scatter STRAIGHT into partitions — the whole
        # group stage (and its per-column [N] passes) disappears.
        # Identical results: keep_group_row is constant within a group, so
        # sum_groups gw * (sum_rows w*x) == sum_rows (w * kg * x).
        kg = s.keep_group_row.astype(dtype)
        wk = w * kg
        spk_safe = jnp.where(s.svalid & s.keep_group_row, s.spk,
                             0).astype(jnp.int32)
        prow = functools.partial(jax.ops.segment_sum,
                                 segment_ids=spk_safe,
                                 num_segments=num_partitions)
        with jax.named_scope("scatter"):
            return PartitionAccumulators(
                pid_count=prow(start_w * kg),
                count=prow(wk) if need_count else zeros,
                sum=prow(vclip * wk) if need_sum else zeros,
                norm_sum=prow(vnorm * wk) if need_norm else zeros,
                norm_sq_sum=prow(vnorm * vnorm * wk)
                if need_norm_sq else zeros,
            )
    keepg_start = (s.is_start & s.svalid & s.keep_group_row).astype(dtype)
    gseg = functools.partial(jax.ops.segment_sum,
                             segment_ids=s.group_id,
                             num_segments=n,
                             indices_are_sorted=True)
    # Each gated-off accumulator saves one full-HBM group pass and one
    # partition pass (the kernel is pass-count bound; module docstring).
    with jax.named_scope("group_reduce"):
        if int_accumulate and sval_i is not None:
            # Narrow-dtype group accumulation (gate:
            # int_accumulation_plan). Counts and clipped sums are exact
            # integers in both domains, so the int32 sums widen to the
            # legacy float32 bits at the fold.
            w_i = s.keep_row.astype(jnp.int32)
            vclip_i = jnp.clip(
                jnp.asarray(value_lo).astype(jnp.int32)
                + sval_i * jnp.asarray(value_scale).astype(jnp.int32),
                int_clip_lo, int_clip_hi)
            g_count = gseg(w_i).astype(dtype) if need_count else None
            g_sum = (jnp.clip(gseg(vclip_i * w_i).astype(dtype),
                              group_clip_lo, group_clip_hi)
                     if need_sum else None)
        else:
            g_count = gseg(w) if need_count else None
            g_sum = (jnp.clip(gseg(vclip * w), group_clip_lo,
                              group_clip_hi)
                     if need_sum else None)
        g_norm = gseg(vnorm * w) if need_norm else None
        g_norm_sq = gseg(vnorm * vnorm * w) if need_norm_sq else None
        g_pk = _group_pk(s, num_partitions, gseg)
        g_keep = gseg(keepg_start)
        gw = (g_keep > 0).astype(dtype)

    # -- kept groups -> per-partition accumulators -------------------------
    g_pk_safe = jnp.where(g_keep > 0, g_pk, 0).astype(jnp.int32)
    pseg = functools.partial(jax.ops.segment_sum,
                             segment_ids=g_pk_safe,
                             num_segments=num_partitions)
    with jax.named_scope("scatter"):
        return PartitionAccumulators(
            pid_count=pseg(gw),
            count=pseg(g_count * gw) if need_count else zeros,
            sum=pseg(g_sum * gw) if need_sum else zeros,
            norm_sum=pseg(g_norm * gw) if need_norm else zeros,
            norm_sq_sum=pseg(g_norm_sq * gw) if need_norm_sq else zeros,
        )


class CompactGroups(NamedTuple):
    """One streamed chunk's per-partition subtotals in compact form.

    Instead of scattering the chunk's kept groups into the full
    [num_partitions] accumulators (a full-HBM partition pass per
    accumulator per chunk), the chunk emits its subtotals as at most
    ``max_groups`` (pk, value) pairs: every distinct partition the chunk
    touches contributes ONE entry per accumulator, already reduced in the
    chunk's group order. ``merge_compact_chunks`` folds any number of
    chunks into the dense accumulators with ONE scatter per accumulator
    column — bit-identical to the legacy per-chunk scatters when the
    group stage is active (the fold order per partition is the same:
    within-chunk group order, then chunk order).

    pk: int32[max_groups]; entries >= num_partitions (padding sentinel)
    or negative (empty runs) are dropped by the merge. The five value
    columns are float32[max_groups]; n_kept is the kept-group count (its
    contract is n_kept <= max_groups — the driver asserts it).
    """
    pk: jnp.ndarray
    pid_count: jnp.ndarray
    count: jnp.ndarray
    sum: jnp.ndarray
    norm_sum: jnp.ndarray
    norm_sq_sum: jnp.ndarray
    n_kept: jnp.ndarray


def _compact_from_groups(kept, g_pk_safe, cols, *, max_groups: int,
                         num_partitions: int, dtype) -> CompactGroups:
    """Compacts per-group accumulator columns (any layout: the sorted
    paths' [n] group slots or the hash path's [n] leader rows) into
    CompactGroups: kept entries pack to a [max_groups] prefix, a stable
    [max_groups] sort by pk groups equal partitions, and a run
    reduction emits ONE subtotal per partition — kept-entry order is
    preserved within a partition, so the sorted paths reproduce the
    legacy scatter's fold order bitwise."""
    g = max_groups
    pos = (jnp.cumsum(kept.astype(jnp.int32)) - 1)
    idx = jnp.where(kept, pos, g)
    cpk = jnp.full((g,), num_partitions, dtype=jnp.int32)
    cpk = cpk.at[idx].set(g_pk_safe, mode="drop")
    ccols = [jnp.zeros((g,), dtype=dtype).at[idx].set(c, mode="drop")
             for c in cols]

    # Stable sort by pk: equal-pk groups stay in kept order, so the run
    # reduction below adds them in exactly the legacy scatter's order.
    sorted_ops = jax.lax.sort([cpk] + ccols, num_keys=1, is_stable=True)
    spk_c = sorted_ops[0]
    is_run_start = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), spk_c[1:] != spk_c[:-1]])
    run_id = (jnp.cumsum(is_run_start) - 1).astype(jnp.int32)
    rseg = functools.partial(jax.ops.segment_sum, segment_ids=run_id,
                             num_segments=g, indices_are_sorted=True)
    run_pk = jax.ops.segment_max(spk_c, run_id, num_segments=g,
                                 indices_are_sorted=True)
    subtot = [rseg(c) for c in sorted_ops[1:]]
    n_kept = jnp.sum(kept.astype(jnp.int32))
    return CompactGroups(run_pk, subtot[0], subtot[1], subtot[2],
                         subtot[3], subtot[4], n_kept)


def _hash_compact_groups(s: BinnedRows, pk: jnp.ndarray, *,
                         num_partitions: int, max_groups: int,
                         row_clip_lo, row_clip_hi, middle, group_clip_lo,
                         group_clip_hi, need_count, need_sum, need_norm,
                         need_norm_sq, has_group_clip, value_is_index,
                         value_lo, value_scale) -> CompactGroups:
    """Compact per-group columns straight out of the hash bins (the
    compact-merge twin of _hash_partition_accumulators): group sums at
    leader rows compact to the shared CompactGroups shape, so the
    merge-side machinery (PR 5) is reused unchanged. Kept-group order
    is row (arrival) order rather than the sorted paths' group order —
    bit-invisible under hash_exact_gate, ULP-only otherwise."""
    sval, _ = _widen_sorted_value(s.sval, value_is_index, value_lo,
                                  value_scale)
    dtype = jnp.promote_types(sval.dtype, jnp.float32)
    vclip = jnp.clip(sval, row_clip_lo, row_clip_hi).astype(dtype)
    vnorm = vclip - middle
    gw = s.lead_row.astype(dtype)
    zeros_n = jnp.zeros_like(gw)
    g_sum = zeros_n
    if need_sum:
        g_sum = _hash_group_sum(s, vclip)
        if has_group_clip:
            g_sum = jnp.clip(g_sum, group_clip_lo, group_clip_hi)
    cols = (gw,
            _hash_group_sum(s, jnp.ones_like(vclip)) * gw
            if need_count else zeros_n,
            g_sum * gw if need_sum else zeros_n,
            _hash_group_sum(s, vnorm) * gw if need_norm else zeros_n,
            _hash_group_sum(s, vnorm * vnorm) * gw
            if need_norm_sq else zeros_n)
    g_pk_safe = jnp.where(s.lead_row, pk, 0).astype(jnp.int32)
    return _compact_from_groups(s.lead_row, g_pk_safe, cols,
                                max_groups=max_groups,
                                num_partitions=num_partitions, dtype=dtype)


@functools.partial(jax.jit,
                   static_argnames=("num_partitions", "max_groups",
                                    "need_count", "need_sum", "need_norm",
                                    "need_norm_sq", "has_group_clip",
                                    "pid_sorted", "max_segments",
                                    "tile_rows", "tile_slack",
                                    "hash_bins", "hash_bin_rows",
                                    "value_is_index", "value_sort_bits",
                                    "int_accumulate"))
def bound_and_aggregate_compact(key: jax.Array,
                                pid: jnp.ndarray,
                                pk: jnp.ndarray,
                                value: jnp.ndarray,
                                valid: jnp.ndarray,
                                *,
                                num_partitions: int,
                                max_groups: int,
                                linf_cap,
                                l0_cap,
                                row_clip_lo,
                                row_clip_hi,
                                middle,
                                group_clip_lo,
                                group_clip_hi,
                                l1_cap=None,
                                need_count: bool = True,
                                need_sum: bool = True,
                                need_norm: bool = True,
                                need_norm_sq: bool = True,
                                has_group_clip: bool = True,
                                pid_sorted: bool = False,
                                max_segments: Optional[int] = None,
                                tile_rows: int = 0,
                                tile_slack: int = 0,
                                hash_bins: int = 0,
                                hash_bin_rows: int = 0,
                                value_is_index: bool = False,
                                value_lo=0.0,
                                value_scale=1.0,
                                value_sort_bits: int = 0,
                                int_accumulate: bool = False,
                                int_clip_lo=None,
                                int_clip_hi=None
                                ) -> CompactGroups:
    """bound_and_aggregate that stops BEFORE the partition scatter.

    Identical sampling to bound_and_aggregate (same sampler, same
    statics, same key) and identical group accumulators; but instead of
    the final [num_partitions] segment-sums it compacts the kept groups
    (<= distinct pids * l0_cap, bounded statically by ``max_groups``),
    stable-sorts them by partition id and reduces each partition's run to
    ONE subtotal — in the kept groups' original order, which is exactly
    the order the legacy partition scatter adds them in. The caller
    merges any number of chunks with merge_compact_chunks.

    With has_group_clip=False the group stage still runs (no clip
    applied); the result equals the legacy direct row->partition scatter
    in exact arithmetic but may differ in float32 ULPs (different
    association), unlike the has_group_clip=True mode which is bitwise.
    """
    n = pid.shape[0]
    # Same trace-time sampler dispatch as bound_and_aggregate (shared
    # _dispatch_sampler) so the sampling decisions replay bitwise.
    with jax.named_scope("group_sort"):
        s = _dispatch_sampler(
            key, pid, pk, valid, linf_cap, l0_cap, l1_cap,
            num_partitions=num_partitions, max_segments=max_segments,
            pid_sorted=pid_sorted, tile_rows=tile_rows,
            tile_slack=tile_slack, hash_bins=hash_bins,
            hash_bin_rows=hash_bin_rows,
            value=_narrow_sort_value(value, value_is_index,
                                     value_sort_bits))
    if isinstance(s, BinnedRows):
        with jax.named_scope("group_reduce"):
            return _hash_compact_groups(
                s, pk, num_partitions=num_partitions,
                max_groups=max_groups, row_clip_lo=row_clip_lo,
                row_clip_hi=row_clip_hi, middle=middle,
                group_clip_lo=group_clip_lo, group_clip_hi=group_clip_hi,
                need_count=need_count, need_sum=need_sum,
                need_norm=need_norm, need_norm_sq=need_norm_sq,
                has_group_clip=has_group_clip,
                value_is_index=value_is_index, value_lo=value_lo,
                value_scale=value_scale)
    sval, sval_i = _widen_sorted_value(s.sval, value_is_index, value_lo,
                                       value_scale)

    dtype = jnp.promote_types(sval.dtype, jnp.float32)
    w = s.keep_row.astype(dtype)
    vclip = jnp.clip(sval, row_clip_lo, row_clip_hi).astype(dtype)
    vnorm = vclip - middle
    keepg_start = (s.is_start & s.svalid & s.keep_group_row).astype(dtype)
    gseg = functools.partial(jax.ops.segment_sum,
                             segment_ids=s.group_id,
                             num_segments=n,
                             indices_are_sorted=True)
    zeros_n = jnp.zeros((n,), dtype=dtype)
    with jax.named_scope("group_reduce"):
        if int_accumulate and sval_i is not None:
            # Same narrow-dtype group accumulation as bound_and_aggregate
            # (gate: int_accumulation_plan; bit-identical widening).
            w_i = s.keep_row.astype(jnp.int32)
            vclip_i = jnp.clip(
                jnp.asarray(value_lo).astype(jnp.int32)
                + sval_i * jnp.asarray(value_scale).astype(jnp.int32),
                int_clip_lo, int_clip_hi)
            g_count = gseg(w_i).astype(dtype) if need_count else None
            g_sum = gseg(vclip_i * w_i).astype(dtype) if need_sum else None
        else:
            g_count = gseg(w) if need_count else None
            g_sum = gseg(vclip * w) if need_sum else None
        if need_sum and has_group_clip:
            g_sum = jnp.clip(g_sum, group_clip_lo, group_clip_hi)
        g_norm = gseg(vnorm * w) if need_norm else None
        g_norm_sq = gseg(vnorm * vnorm * w) if need_norm_sq else None
        g_pk = _group_pk(s, num_partitions, gseg)
        g_keep = gseg(keepg_start)
        gw = (g_keep > 0).astype(dtype)
        g_pk_safe = jnp.where(g_keep > 0, g_pk, 0).astype(jnp.int32)

    # The same scatter operands the legacy partition pass would feed
    # (value * gw, in group order) — compacted instead of scattered.
    cols = (gw,
            g_count * gw if need_count else zeros_n,
            g_sum * gw if need_sum else zeros_n,
            g_norm * gw if need_norm else zeros_n,
            g_norm_sq * gw if need_norm_sq else zeros_n)

    with jax.named_scope("compact"):
        return _compact_from_groups(g_keep > 0, g_pk_safe, cols,
                                    max_groups=max_groups,
                                    num_partitions=num_partitions,
                                    dtype=dtype)


@functools.partial(jax.jit, static_argnames=("num_partitions",
                                             "need_flags"))
def merge_compact_chunks(accs: PartitionAccumulators,
                         pk: jnp.ndarray,
                         pid_count: jnp.ndarray,
                         count: jnp.ndarray,
                         sum_: jnp.ndarray,
                         norm_sum: jnp.ndarray,
                         norm_sq_sum: jnp.ndarray,
                         *,
                         num_partitions: int,
                         need_flags=(True, True, True, True)
                         ) -> PartitionAccumulators:
    """ONE [num_partitions] scatter per accumulator merges every chunk.

    Inputs are [n_chunks, max_groups] stacks of CompactGroups columns.
    The flatten is chunk-major, so per partition the scatter adds the
    chunk subtotals in chunk order on top of ``accs`` — reproducing the
    legacy loop's ``accs = accs + chunk_scatter`` fold bitwise (each
    chunk contributes at most one entry per partition). Sentinel /
    negative pk entries drop.
    """
    flat_pk = pk.reshape(-1)

    def scat(base, col):
        return base.at[flat_pk].add(col.reshape(-1), mode="drop")

    with jax.named_scope("scatter"):
        return PartitionAccumulators(
            pid_count=scat(accs.pid_count, pid_count),
            count=scat(accs.count, count) if need_flags[0] else accs.count,
            sum=scat(accs.sum, sum_) if need_flags[1] else accs.sum,
            norm_sum=(scat(accs.norm_sum, norm_sum)
                      if need_flags[2] else accs.norm_sum),
            norm_sq_sum=(scat(accs.norm_sq_sum, norm_sq_sum)
                         if need_flags[3] else accs.norm_sq_sum),
        )


def compact_group_bound(cap: int, ucap: int, l0_cap) -> Optional[int]:
    """Static kept-group bound for one chunk, or None when unavailable.

    Kept groups per pid-disjoint chunk <= distinct pids * l0_cap, and the
    RLE wire format bounds distinct pids per bucket by its entry capacity
    (ucap); total groups are also <= the row capacity (cap). Requires a
    concrete (host) l0_cap — a traced value cannot size a static shape.
    """
    try:
        l0 = int(l0_cap)
    except (TypeError, ValueError):
        return None
    if l0 < 1:
        return None
    bound = min(int(cap), int(ucap) * l0)
    return max(8, (bound + 7) & ~7)


def _group_pk(s: SampledRows, num_partitions: int, gseg) -> jnp.ndarray:
    """Each group slot's partition id: a float32-reduced column when ids
    fit float32 exactly (< 2^24), an integer pass otherwise. Always
    float32 regardless of the value dtype — a narrower accumulation dtype
    (e.g. float16 values) must never round partition ids. Single
    definition so the scalar and vector kernels can never diverge on the
    precision threshold or the padding mask."""
    if num_partitions < (1 << 24):
        start_w = (s.is_start & s.svalid).astype(jnp.float32)
        return gseg(start_w *
                    jnp.where(s.svalid, s.spk, 0).astype(jnp.float32))
    start_w_i = (s.is_start & s.svalid).astype(jnp.int32)
    return gseg(jnp.where(s.svalid, s.spk, 0) * start_w_i)


@functools.partial(jax.jit, static_argnames=("num_partitions", "norm_ord",
                                             "pid_sorted", "max_segments"))
def bound_and_aggregate_vector(key: jax.Array,
                               pid: jnp.ndarray,
                               pk: jnp.ndarray,
                               value: jnp.ndarray,
                               valid: jnp.ndarray,
                               *,
                               num_partitions: int,
                               linf_cap,
                               l0_cap,
                               max_norm,
                               norm_ord: int,
                               l1_cap=None,
                               pid_sorted: bool = False,
                               max_segments: Optional[int] = None
                               ) -> tuple[jnp.ndarray, PartitionAccumulators]:
    """VECTOR_SUM path: per-row norm clipping + the same two-stage sampling.

    value: float32[N, D]. norm_ord: 0 => Linf clip per coordinate, 1/2 =>
    L1/L2 norm scaling. Returns (vector_sums [num_partitions, D],
    scalar PartitionAccumulators) — the scalar accumulators ride along so
    callers never need a second pass over the rows.

    pid_sorted: the presorted-ingest contract of
    _sample_rows_and_groups_presorted holds (pid nondecreasing over a
    valid prefix); the sampler then runs the packed 3-key sort shared
    with the scalar path (_pack_key_bits layout) carrying only the row
    order — 4 sort operands instead of the general path's 7; the [N, D]
    vector payload is gathered once by the sorted order either way. Same
    sampling distribution, different draws; ignored in L1 mode.
    """
    n = pid.shape[0]
    d = value.shape[1]
    if n == 0:
        zeros = jnp.zeros((num_partitions,), dtype=value.dtype)
        return (jnp.zeros((num_partitions, d), dtype=value.dtype),
                PartitionAccumulators(zeros, zeros, zeros, zeros, zeros))
    s = _dispatch_sampler(key, pid, pk, valid, linf_cap, l0_cap, l1_cap,
                          num_partitions=num_partitions,
                          max_segments=max_segments,
                          pid_sorted=pid_sorted, tile_rows=0, tile_slack=0,
                          value=None, need_order=True)
    sval = value[s.order]

    if norm_ord == 0:
        sval = jnp.clip(sval, -max_norm, max_norm)
    else:
        norms = jnp.linalg.norm(sval, ord=norm_ord, axis=1, keepdims=True)
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norms, 1e-30))
        sval = sval * scale

    dtype = jnp.promote_types(sval.dtype, jnp.float32)
    sval = sval.astype(dtype)
    w1 = s.keep_row.astype(dtype)
    keepg_start = (s.is_start & s.svalid & s.keep_group_row).astype(dtype)
    gseg = functools.partial(jax.ops.segment_sum,
                             segment_ids=s.group_id,
                             num_segments=n,
                             indices_are_sorted=True)
    # The [N, D] vector payload is one segment-sum (D is a real data axis,
    # already tile-friendly); scalar columns go per pass like the scalar
    # kernel.
    g_vec = gseg(sval * w1[:, None])
    g_count = gseg(w1)
    g_pk = _group_pk(s, num_partitions, gseg)
    g_keep = gseg(keepg_start)
    gw = (g_keep > 0).astype(dtype)
    g_pk_safe = jnp.where(g_keep > 0, g_pk, 0).astype(jnp.int32)
    pseg = functools.partial(jax.ops.segment_sum,
                             segment_ids=g_pk_safe,
                             num_segments=num_partitions)
    vector_sums = pseg(g_vec * gw[:, None])
    zeros = jnp.zeros((num_partitions,), dtype=dtype)
    accs = PartitionAccumulators(pid_count=pseg(gw),
                                 count=pseg(g_count * gw),
                                 sum=zeros,
                                 norm_sum=zeros,
                                 norm_sq_sum=zeros)
    return vector_sums, accs


@functools.partial(jax.jit,
                   static_argnames=("pid_sorted", "max_segments",
                                    "num_partitions", "tile_rows",
                                    "tile_slack", "hash_bins",
                                    "hash_bin_rows"))
def bound_row_mask(key: jax.Array, pid: jnp.ndarray, pk: jnp.ndarray,
                   valid: jnp.ndarray, linf_cap, l0_cap,
                   l1_cap=None, *, pid_sorted: bool = False,
                   max_segments: Optional[int] = None,
                   num_partitions: Optional[int] = None,
                   tile_rows: int = 0,
                   tile_slack: int = 0,
                   hash_bins: int = 0,
                   hash_bin_rows: int = 0) -> jnp.ndarray:
    """Per-row keep mask (original row order) after Linf + L0 bounding.

    Identical sampling decisions to bound_and_aggregate for the same key —
    guaranteed structurally: all bounding kernels derive from the shared
    _sample_rows_and_groups pipeline (pass the SAME pid_sorted /
    max_segments / num_partitions statics as the aggregation kernel so the
    two sort with identical keys). This one returns which rows survive
    instead of aggregates — the row-level view needed by consumers that
    histogram individual contributions (e.g. the batched quantile trees of
    ops/quantiles.py).
    """
    n = pid.shape[0]
    if n == 0:
        return jnp.zeros((0,), dtype=bool)
    # Same trace-time sampler dispatch as bound_and_aggregate (shared
    # _dispatch_sampler, incl. the tiled path) so replayed sampling stays
    # identical.
    s = _dispatch_sampler(
        key, pid, pk, valid, linf_cap, l0_cap, l1_cap,
        num_partitions=num_partitions if num_partitions is not None else 0,
        max_segments=max_segments,
        pid_sorted=pid_sorted and num_partitions is not None,
        tile_rows=tile_rows, tile_slack=tile_slack,
        hash_bins=hash_bins, hash_bin_rows=hash_bin_rows, value=None,
        need_order=True)
    if isinstance(s, BinnedRows):
        # The hash-binned decisions are already in original row order.
        return s.keep_row & s.keep_group_row
    keep_sorted_rows = s.keep_row & s.keep_group_row
    return jnp.zeros((n,), dtype=bool).at[s.order].set(keep_sorted_rows)


@functools.partial(jax.jit, static_argnames=("num_partitions",))
def count_distinct_pids_per_partition(pid: jnp.ndarray, pk: jnp.ndarray,
                                      valid: jnp.ndarray, key: jax.Array,
                                      l0_cap, *,
                                      num_partitions: int) -> jnp.ndarray:
    """select_partitions fast path: L0-bounded distinct-pid counts per pk."""
    accs = bound_and_aggregate(key,
                               pid,
                               pk,
                               jnp.zeros_like(pid, dtype=jnp.float32),
                               valid,
                               num_partitions=num_partitions,
                               linf_cap=1,
                               l0_cap=l0_cap,
                               row_clip_lo=-jnp.inf,
                               row_clip_hi=jnp.inf,
                               middle=0.0,
                               group_clip_lo=-jnp.inf,
                               group_clip_hi=jnp.inf,
                               need_count=False,
                               need_sum=False,
                               need_norm=False,
                               need_norm_sq=False,
                               has_group_clip=False)
    return accs.pid_count
