"""Multi-chip execution: shard_map over a 2D ('dp', 'mp') device mesh.

This is the TPU-native replacement for the reference's distributed shuffle
(Beam runner / Spark shuffle behind group_by_key and
combine_accumulators_per_key, pipeline_backend.py:223-474; SURVEY.md §2.5):

  * rows are sharded over all mesh devices (data parallelism across both
    axes) — the host loader hash-shards rows by privacy id, so each privacy
    unit's rows are local to one device and contribution bounding is exact
    without any cross-device exchange;
  * each device runs the fused bound-and-aggregate kernel on its shard,
    producing per-partition partial accumulators [padded_p];
  * partials are combined with `psum_scatter` over 'dp' then 'mp' — the
    reduce-scatter rides ICI and leaves every device holding the *full* sum
    for a distinct 1/(dp*mp) slice of the partition space (this is the
    shuffle);
  * the returned accumulators are global jax.Arrays sharded over the
    partition dimension, so everything downstream — partition selection,
    per-mechanism noise, metric math — runs sharded too under XLA's SPMD
    partitioner without further shard_map plumbing.

JaxDPEngine(mesh=...) routes its fused kernel through here; every metric,
selection strategy, and noise mechanism the engine supports works on any
mesh shape unchanged. __graft_entry__.dryrun_multichip exercises the full
engine path on a virtual CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pipelinedp_tpu.ops import columnar
from pipelinedp_tpu.ops import quantiles as quantile_ops
from pipelinedp_tpu.runtime import driver as driver_lib


def _spec(mesh: Mesh) -> P:
    """Row arrays shard over every mesh axis (dcn included)."""
    return P(tuple(mesh.axis_names))


def _scatter_axes(mesh: Mesh) -> tuple:
    """Reduce-scatter order: ICI axes first, 'dcn' last, so the partials
    crossing the slow inter-slice links are already reduced within each
    slice (payload shrinks by dp*mp before touching DCN)."""
    axes = tuple(a for a in mesh.axis_names if a != "dcn")
    if "dcn" in mesh.axis_names:
        axes += ("dcn",)
    return axes


def _part_spec(mesh: Mesh) -> P:
    """Partition-dimension layout after the reduce-scatter: must list the
    axes in scatter order for the chunks to assemble correctly."""
    return P(_scatter_axes(mesh))


def make_mesh(n_devices: Optional[int] = None,
              dp: Optional[int] = None,
              mp: Optional[int] = None,
              devices=None,
              n_slices: int = 1) -> Mesh:
    """Builds a ('dp', 'mp') mesh — or ('dcn', 'dp', 'mp') with n_slices>1
    — over the available devices.

    Default factorization puts the larger factor on 'dp' (rows usually
    outnumber partitions per device). The 'dcn' axis models multi-slice /
    multi-host deployments: devices within a slice talk over ICI, slices
    over DCN, and the reduce-scatter runs intra-slice first so only
    already-reduced partition partials cross the slow links.
    """
    if devices is None:
        devices = jax.devices()
    n = n_devices or len(devices)
    if n_slices > 1 and n % n_slices != 0:
        raise ValueError(f"n_devices={n} not divisible by "
                         f"n_slices={n_slices}")
    per_slice = n // n_slices
    if dp is None or mp is None:
        mp = 1
        for candidate in range(int(np.sqrt(per_slice)), 0, -1):
            if per_slice % candidate == 0:
                mp = candidate
                break
        dp = per_slice // mp
    if dp * mp != per_slice:
        raise ValueError(f"dp*mp={dp*mp} != devices per slice={per_slice}")
    if n_slices > 1:
        return Mesh(
            np.asarray(devices[:n]).reshape(n_slices, dp, mp),
            ("dcn", "dp", "mp"))
    return Mesh(np.asarray(devices[:n]).reshape(dp, mp), ("dp", "mp"))


def padded_num_partitions(mesh: Mesh, num_partitions: int) -> int:
    """num_partitions rounded up so the partition dim shards evenly."""
    n_dev = mesh.devices.size
    return ((num_partitions + n_dev - 1) // n_dev) * n_dev


def shard_rows_by_pid(pid: np.ndarray,
                      pk: np.ndarray,
                      value: np.ndarray,
                      n_shards: int,
                      valid: Optional[np.ndarray] = None
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray]:
    """Host-side loader step: hash-shard rows by privacy id and pad shards
    to equal length.

    Returns arrays of shape [n_shards * shard_len] laid out shard-major,
    plus the validity mask for padding rows. Keeping each pid on one shard
    makes L0/Linf bounding exact with zero cross-device row exchange.
    """
    # Multiplicative hash, not bare modulo: raw (unfactorized) id spaces
    # are often structured (all-even ids, per-site ranges) and would skew
    # a low-bits split, doubling shard padding.
    hashed = ((pid.astype(np.uint32) * np.uint32(2654435761)) >>
              np.uint32(16))
    shard_of_row = hashed % np.uint32(n_shards)
    order = np.argsort(shard_of_row, kind="stable")
    pid, pk, value = pid[order], pk[order], value[order]
    valid = (np.ones(len(pid), dtype=bool)
             if valid is None else np.asarray(valid)[order])
    shard_of_row = shard_of_row[order]
    counts = np.bincount(shard_of_row, minlength=n_shards)
    shard_len = int(counts.max()) if len(pid) else 1
    total = n_shards * shard_len
    out_pid = np.zeros(total, dtype=pid.dtype)
    out_pk = np.zeros(total, dtype=pk.dtype)
    out_val = np.zeros((total,) + value.shape[1:], dtype=value.dtype)
    out_valid = np.zeros(total, dtype=bool)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    for s in range(n_shards):
        lo, n_rows = offsets[s], counts[s]
        dst = s * shard_len
        out_pid[dst:dst + n_rows] = pid[lo:lo + n_rows]
        out_pk[dst:dst + n_rows] = pk[lo:lo + n_rows]
        out_val[dst:dst + n_rows] = value[lo:lo + n_rows]
        out_valid[dst:dst + n_rows] = valid[lo:lo + n_rows]
    return out_pid, out_pk, out_val, out_valid


def _device_key(key, axes):
    """Independent PRNG stream per mesh position."""
    for axis in axes:
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))
    return key


def _reduce_scatter(x, scatter_axes):
    # Scatter in _scatter_axes order (ICI first, DCN last): each hop moves
    # already-partially-reduced data, and the chunk each device ends up
    # holding matches the _part_spec output layout.
    for axis in scatter_axes:
        x = jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    return x


@functools.lru_cache(maxsize=None)
def _scalar_kernel(mesh: Mesh, padded_p: int, has_l1: bool = False,
                   need_flags=(True, True, True, True),
                   has_group_clip: bool = True):
    """Sharded twin of columnar.bound_and_aggregate for a given mesh.

    has_l1 compiles the max_contributions variant (an extra runtime l1_cap
    scalar and the per-pid total sample in the local kernel) — shards are
    pid-disjoint, so per-shard L1 sampling is exact.
    """

    axes = tuple(mesh.axis_names)
    scatter = _scatter_axes(mesh)

    def local_step(key, pid, pk, value, valid, linf_cap, l0_cap, row_clip_lo,
                   row_clip_hi, middle, group_clip_lo, group_clip_hi,
                   *l1_args):
        accs = columnar.bound_and_aggregate(
            _device_key(key, axes), pid, pk, value, valid,
            num_partitions=padded_p,
            linf_cap=linf_cap,
            l0_cap=l0_cap,
            row_clip_lo=row_clip_lo,
            row_clip_hi=row_clip_hi,
            middle=middle,
            group_clip_lo=group_clip_lo,
            group_clip_hi=group_clip_hi,
            l1_cap=l1_args[0] if has_l1 else None,
            need_count=need_flags[0],
            need_sum=need_flags[1],
            need_norm=need_flags[2],
            need_norm_sq=need_flags[3],
            has_group_clip=has_group_clip)
        return jax.tree.map(lambda x: _reduce_scatter(x, scatter), accs)

    spec = _spec(mesh)
    part = _part_spec(mesh)
    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(),) + (spec,) * 4 + (P(),) * (8 if has_l1 else 7),
        out_specs=columnar.PartitionAccumulators(*([part] * 5)),
        check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _vector_kernel(mesh: Mesh, padded_p: int, norm_ord: int,
                   has_l1: bool = False, pid_sorted: bool = False,
                   max_segments=None):
    """Sharded twin of columnar.bound_and_aggregate_vector.

    pid_sorted: every device's local block is pid-nondecreasing over its
    valid prefix (the host pre-sorted rows by pid before the stable
    shard partition of shard_rows_by_pid, which preserves in-shard
    order), so the local sampler runs the packed 3-key sort shared with
    the scalar path; max_segments bounds the distinct pids of any one
    shard (the global distinct-pid count is always valid)."""

    axes = tuple(mesh.axis_names)
    scatter = _scatter_axes(mesh)

    def local_step(key, pid, pk, value, valid, linf_cap, l0_cap, max_norm,
                   *l1_args):
        vector_sums, accs = columnar.bound_and_aggregate_vector(
            _device_key(key, axes), pid, pk, value, valid,
            num_partitions=padded_p,
            linf_cap=linf_cap,
            l0_cap=l0_cap,
            max_norm=max_norm,
            norm_ord=norm_ord,
            l1_cap=l1_args[0] if has_l1 else None,
            pid_sorted=pid_sorted,
            max_segments=max_segments)
        return (_reduce_scatter(vector_sums, scatter),
                jax.tree.map(lambda x: _reduce_scatter(x, scatter), accs))

    spec = _spec(mesh)
    part = _part_spec(mesh)
    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(),) + (spec,) * 4 + (P(),) * (4 if has_l1 else 3),
        out_specs=(part,
                   columnar.PartitionAccumulators(*([part] * 5))),
        check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _quantile_kernel(mesh: Mesh, padded_p: int, num_leaves: int,
                     has_l1: bool = False):
    """Sharded leaf-histogram kernel for the batched quantile trees."""

    axes = tuple(mesh.axis_names)
    scatter = _scatter_axes(mesh)

    def local_step(key, pid, pk, value, valid, linf_cap, l0_cap, lower,
                   upper, *l1_args):
        mask = columnar.bound_row_mask(_device_key(key, axes), pid, pk,
                                       valid, linf_cap, l0_cap,
                                       l1_cap=l1_args[0] if has_l1 else None)
        hist = quantile_ops.leaf_histograms(pk, value, mask,
                                            num_partitions=padded_p,
                                            num_leaves=num_leaves,
                                            lower=lower,
                                            upper=upper)
        return _reduce_scatter(hist, scatter)

    spec = _spec(mesh)
    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(),) + (spec,) * 4 + (P(),) * (5 if has_l1 else 4),
        out_specs=_part_spec(mesh),
        check_vma=False)
    return jax.jit(fn)


def quantile_leaf_histograms(mesh: Mesh, key, pid, pk, value, valid, *,
                             num_partitions: int, num_leaves: int, lower,
                             upper, linf_cap, l0_cap, l1_cap=None):
    """Multi-chip [padded_p, num_leaves] quantile-tree leaf counts."""
    padded_p = padded_num_partitions(mesh, num_partitions)
    dpid, dpk, dval, dvalid = _shard_and_put(mesh, pid, pk, value, valid)
    kernel = _quantile_kernel(mesh, padded_p, num_leaves,
                              has_l1=l1_cap is not None)
    args = (key, dpid, dpk, dval, dvalid, linf_cap, l0_cap, float(lower),
            float(upper))
    if l1_cap is not None:
        args += (l1_cap,)
    return kernel(*args)


def host_row_mask(mesh: Mesh, key, pid, pk, *, linf_cap, l0_cap,
                  l1_cap=None) -> np.ndarray:
    """Contribution-bounding keep mask for host rows, computed on the mesh.

    The custom-combiner path under mesh=: rows are hash-sharded by privacy
    id (pid-disjoint shards make Linf/L0/L1 sampling per shard exact —
    same argument as ops/streaming.py), the sharded row-mask kernel runs
    on every device, and the mask comes back scattered to the caller's row
    order. Only the two id columns ship; the value column stays on host so
    user combiners keep exact float64 inputs (reference behavior: custom
    combiners run on every backend, combiners.py:925).
    """
    pid = np.asarray(pid)
    pk = np.asarray(pk, dtype=np.int32)
    n = len(pid)
    if n == 0:
        return np.zeros(0, dtype=bool)
    n_dev = mesh.devices.size
    hashed = ((pid.astype(np.uint32) * np.uint32(2654435761)) >>
              np.uint32(16))
    shard_of_row = hashed % np.uint32(n_dev)
    order = np.argsort(shard_of_row, kind="stable")
    counts = np.bincount(shard_of_row, minlength=n_dev)
    shard_len = int(counts.max())
    total = n_dev * shard_len
    spid = np.zeros(total, dtype=np.int32)
    spk = np.zeros(total, dtype=np.int32)
    svalid = np.zeros(total, dtype=bool)
    # staged slot -> original row (for the scatter back).
    src = np.zeros(total, dtype=np.int64)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    for s in range(n_dev):
        lo, m = offsets[s], counts[s]
        dst = s * shard_len
        rows = order[lo:lo + m]
        spid[dst:dst + m] = pid[rows]
        spk[dst:dst + m] = pk[rows]
        svalid[dst:dst + m] = True
        src[dst:dst + m] = rows
    sharding = NamedSharding(mesh, _spec(mesh))
    dpid, dpk, dvalid = (jax.device_put(a, sharding)
                         for a in (spid, spk, svalid))
    kernel = _row_mask_kernel(mesh, has_l1=l1_cap is not None)
    args = (key, dpid, dpk, dvalid, linf_cap, l0_cap)
    if l1_cap is not None:
        args += (l1_cap,)
    staged_mask = np.asarray(kernel(*args))
    out = np.zeros(n, dtype=bool)
    out[src[svalid]] = staged_mask[svalid]
    return out


@functools.lru_cache(maxsize=None)
def _row_mask_kernel(mesh: Mesh, has_l1: bool = False):
    """Sharded contribution-bounding row mask (row-sharded in and out).

    One sampling pass shared by every partition block of the blocked
    quantile path — the expensive per-device sorts run once, not once per
    block."""

    axes = tuple(mesh.axis_names)

    def local_step(key, pid, pk, valid, linf_cap, l0_cap, *l1_args):
        return columnar.bound_row_mask(_device_key(key, axes), pid, pk,
                                       valid, linf_cap, l0_cap,
                                       l1_cap=l1_args[0] if has_l1 else None)

    spec = _spec(mesh)
    fn = jax.shard_map(local_step,
                       mesh=mesh,
                       in_specs=(P(),) + (spec,) * 3 + (P(),) *
                       (3 if has_l1 else 2),
                       out_specs=spec,
                       check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _local_pk_sort_kernel(mesh: Mesh):
    """Sorts each device's rows by partition id (one argsort + gathers) so
    the per-block kernels can window a contiguous row range instead of
    rescanning every row for every block."""

    def local_step(pk, value, mask):
        order = jnp.argsort(pk)
        return pk[order], value[order], mask[order]

    spec = _spec(mesh)
    fn = jax.shard_map(local_step,
                       mesh=mesh,
                       in_specs=(spec,) * 3,
                       out_specs=(spec,) * 3,
                       check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _block_rows_cap_kernel(mesh: Mesh, block_p: int, n_blocks: int):
    """Max rows any device holds for any partition block (replicated
    scalar) — the static window size of the block-histogram kernel."""

    axes = tuple(mesh.axis_names)

    def local_step(spk, mask):
        block_of_row = jnp.minimum(spk // block_p, n_blocks - 1)
        counts = jax.ops.segment_sum(mask.astype(jnp.int32), block_of_row,
                                     num_segments=n_blocks,
                                     indices_are_sorted=True)
        m = counts.max()
        for axis in axes:
            m = jax.lax.pmax(m, axis)
        return m

    spec = _spec(mesh)
    fn = jax.shard_map(local_step,
                       mesh=mesh,
                       in_specs=(spec, spec),
                       out_specs=P(),
                       check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _block_hist_kernel(mesh: Mesh, block_p: int, num_leaves: int,
                       window: int):
    """Sharded [block_p, num_leaves] leaf histogram of one partition block
    [p0, p0 + block_p) over pk-sorted local rows: each device slices the
    `window` rows starting at its block boundary (searchsorted), so a
    block's cost is proportional to the window, not the full row set."""

    scatter = _scatter_axes(mesh)

    def local_step(spk, value, mask, p0, lower, upper):
        n_local = spk.shape[0]
        start = jnp.searchsorted(spk, p0).astype(jnp.int32)
        start = jnp.clip(start, 0, max(n_local - window, 0))
        wpk = jax.lax.dynamic_slice_in_dim(spk, start, window)
        wval = jax.lax.dynamic_slice_in_dim(value, start, window)
        wmask = jax.lax.dynamic_slice_in_dim(mask, start, window)
        in_block = wmask & (wpk >= p0) & (wpk < p0 + block_p)
        local_pk = jnp.clip(wpk - p0, 0, block_p - 1)
        hist = quantile_ops.leaf_histograms(local_pk, wval, in_block,
                                            num_partitions=block_p,
                                            num_leaves=num_leaves,
                                            lower=lower,
                                            upper=upper)
        return _reduce_scatter(hist, scatter)

    spec = _spec(mesh)
    fn = jax.shard_map(local_step,
                       mesh=mesh,
                       in_specs=(spec,) * 3 + (P(),) * 3,
                       out_specs=_part_spec(mesh),
                       check_vma=False)
    return jax.jit(fn)


def blocked_quantile_columns(mesh: Mesh, key, pid, pk, value, valid, *,
                             num_partitions: int, num_leaves: int, lower,
                             upper, linf_cap, l0_cap, num_quantiles: int,
                             finish_fn, l1_cap=None) -> np.ndarray:
    """[num_partitions, num_quantiles] DP quantiles on the mesh, blocked.

    Mesh twin of ops/quantiles.blocked_quantile_columns for partition
    counts whose dense [partitions, leaves] layout exceeds the device
    budget: the contribution-bounding mask is computed once (sharded), each
    device sorts its rows by pk once, and each partition block histograms
    only a contiguous row window (searchsorted + dynamic slice, padded to
    the max per-device block population so one kernel serves every block).
    The [block_p, num_leaves] result feeds finish_fn (noise + tree walk) —
    identical released values to the dense path, bounded memory. The
    eps/delta split is per tree, so per-block noising composes exactly.
    """
    n_dev = mesh.devices.size
    block_p = max(1, quantile_ops.MAX_HISTOGRAM_ELEMENTS // num_leaves)
    block_p = max(n_dev, (block_p // n_dev) * n_dev)
    n_blocks = (num_partitions + block_p - 1) // block_p
    dpid, dpk, dval, dvalid = _shard_and_put(mesh, pid, pk, value, valid)
    mask_kernel = _row_mask_kernel(mesh, has_l1=l1_cap is not None)
    args = (key, dpid, dpk, dvalid, linf_cap, l0_cap)
    if l1_cap is not None:
        args += (l1_cap,)
    mask = mask_kernel(*args)
    spk, sval, smask = _local_pk_sort_kernel(mesh)(dpk, dval, mask)
    n_local = int(np.asarray(dpk.shape[0])) // n_dev
    # Window = max per-device rows in any block, rounded up to a power of
    # two (few compiled shapes); counting masked-out rows too keeps the
    # window an upper bound on any block's slice.
    cap = int(
        _block_rows_cap_kernel(mesh, block_p, n_blocks)(
            spk, jnp.ones_like(smask)))
    window = 1 << max(cap - 1, 0).bit_length()
    window = int(min(max(window, 1024), max(n_local, 1)))
    hist_kernel = _block_hist_kernel(mesh, block_p, num_leaves, window)
    out = np.zeros((num_partitions, num_quantiles), dtype=np.float64)
    for p0 in range(0, num_partitions, block_p):
        p1 = min(p0 + block_p, num_partitions)
        hist = hist_kernel(spk, sval, smask, p0, float(lower), float(upper))
        out[p0:p1] = np.asarray(finish_fn(hist))[:p1 - p0]
    return out


def _shard_and_put(mesh: Mesh, pid, pk, value, valid):
    """Stages host rows onto the mesh; passes through already-staged
    jax.Arrays so callers running several kernels over the same rows (e.g.
    aggregate + quantile histogram) pay the host shuffle and transfer once.
    """
    if isinstance(pid, jax.Array):
        return pid, pk, value, valid
    n_dev = mesh.devices.size
    spid, spk, sval, svalid = shard_rows_by_pid(np.asarray(pid),
                                                np.asarray(pk),
                                                np.asarray(value), n_dev,
                                                np.asarray(valid))
    sharding = NamedSharding(mesh, _spec(mesh))
    return tuple(
        jax.device_put(a, sharding) for a in (spid, spk, sval, svalid))


def stage_rows(mesh: Mesh, pid, pk, value, valid):
    """Public staging step: hash-shard + device_put once, reuse across
    kernels."""
    return _shard_and_put(mesh, pid, pk, value, valid)


def bound_and_aggregate(mesh: Mesh,
                        key: jax.Array,
                        pid: np.ndarray,
                        pk: np.ndarray,
                        value: np.ndarray,
                        valid: np.ndarray,
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
                        need_flags=(True, True, True, True),
                        has_group_clip: bool = True
                        ) -> columnar.PartitionAccumulators:
    """Multi-chip bound-and-aggregate: host rows in, global sharded
    [padded_p] accumulators out (padding partitions are all-zero; callers
    trim to num_partitions when materializing)."""
    padded_p = padded_num_partitions(mesh, num_partitions)
    dpid, dpk, dval, dvalid = _shard_and_put(mesh, pid, pk, value, valid)
    kernel = _scalar_kernel(mesh, padded_p, has_l1=l1_cap is not None,
                            need_flags=tuple(need_flags),
                            has_group_clip=has_group_clip)
    args = (key, dpid, dpk, dval, dvalid, linf_cap, l0_cap,
            float(row_clip_lo), float(row_clip_hi), float(middle),
            float(group_clip_lo), float(group_clip_hi))
    if l1_cap is not None:
        args += (l1_cap,)
    return kernel(*args)


@functools.lru_cache(maxsize=None)
def _codec_scalar_kernel(mesh: Mesh, padded_p: int, fmt, has_l1: bool,
                         need_flags, has_group_clip: bool,
                         int_clip=None):
    """Wire-codec decode + bound-and-aggregate, shard-local.

    Each device receives ONE codec bucket row of the [n_dev, W] slab,
    decodes it with elementwise ops (ops/wirecodec.decode_bucket), runs
    the fused kernel, and reduce-scatters the per-partition partials —
    the multi-chip twin of streaming._chunk_step_rle. fmt carries the
    segment-local sort tile geometry (streaming.finish_wire_plan);
    int_clip is the static int32 row-clip pair of the int-accumulation
    gate, or None for the float32 accumulators."""
    from pipelinedp_tpu.ops import streaming

    axes = tuple(mesh.axis_names)
    scatter_axes = _scatter_axes(mesh)

    def local_step(key, row, n_valid, n_uniq, linf_cap, l0_cap, row_clip_lo,
                   row_clip_hi, middle, group_clip_lo, group_clip_hi,
                   *l1_args):
        pid, pk, value, valid, vkw = streaming._decode_for_kernel(
            row[0], n_valid[0], n_uniq[0], fmt)
        accs = columnar.bound_and_aggregate(
            _device_key(key, axes), pid, pk, value, valid,
            num_partitions=padded_p,
            linf_cap=linf_cap,
            l0_cap=l0_cap,
            row_clip_lo=row_clip_lo,
            row_clip_hi=row_clip_hi,
            middle=middle,
            group_clip_lo=group_clip_lo,
            group_clip_hi=group_clip_hi,
            l1_cap=l1_args[0] if has_l1 else None,
            need_count=need_flags[0],
            need_sum=need_flags[1],
            need_norm=need_flags[2],
            need_norm_sq=need_flags[3],
            has_group_clip=has_group_clip,
            pid_sorted=fmt.pid_sorted,
            max_segments=fmt.ucap if fmt.pid_sorted else None,
            int_accumulate=int_clip is not None,
            int_clip_lo=int_clip[0] if int_clip is not None else None,
            int_clip_hi=int_clip[1] if int_clip is not None else None,
            **vkw)
        return columnar.PartitionAccumulators(
            *(_reduce_scatter(a, scatter_axes) for a in accs))

    spec = _spec(mesh)
    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), spec, spec, spec) + (P(),) * (8 if has_l1 else 7),
        out_specs=columnar.PartitionAccumulators(*(_part_spec(mesh),) * 5),
        check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _codec_compact_kernel(mesh: Mesh, padded_p: int, fmt, max_groups: int,
                          has_l1: bool, need_flags,
                          has_group_clip: bool, int_clip=None):
    """Compact-merge twin of _codec_scalar_kernel: each device decodes its
    bucket and emits compact per-group subtotal columns
    (columnar.CompactGroups, [max_groups] per device) instead of
    scattering into [padded_p] and reduce-scattering per chunk. The
    per-chunk collectives move to the single merge kernel below."""
    from pipelinedp_tpu.ops import streaming

    axes = tuple(mesh.axis_names)

    def local_step(key, row, n_valid, n_uniq, linf_cap, l0_cap, row_clip_lo,
                   row_clip_hi, middle, group_clip_lo, group_clip_hi,
                   *l1_args):
        pid, pk, value, valid, vkw = streaming._decode_for_kernel(
            row[0], n_valid[0], n_uniq[0], fmt)
        cg = columnar.bound_and_aggregate_compact(
            _device_key(key, axes), pid, pk, value, valid,
            num_partitions=padded_p,
            max_groups=max_groups,
            linf_cap=linf_cap,
            l0_cap=l0_cap,
            row_clip_lo=row_clip_lo,
            row_clip_hi=row_clip_hi,
            middle=middle,
            group_clip_lo=group_clip_lo,
            group_clip_hi=group_clip_hi,
            l1_cap=l1_args[0] if has_l1 else None,
            need_count=need_flags[0],
            need_sum=need_flags[1],
            need_norm=need_flags[2],
            need_norm_sq=need_flags[3],
            has_group_clip=has_group_clip,
            pid_sorted=fmt.pid_sorted,
            max_segments=fmt.ucap if fmt.pid_sorted else None,
            int_accumulate=int_clip is not None,
            int_clip_lo=int_clip[0] if int_clip is not None else None,
            int_clip_hi=int_clip[1] if int_clip is not None else None,
            **vkw)
        return columnar.CompactGroups(
            cg.pk, cg.pid_count, cg.count, cg.sum, cg.norm_sum,
            cg.norm_sq_sum, jnp.reshape(cg.n_kept, (1,)))

    spec = _spec(mesh)
    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), spec, spec, spec) + (P(),) * (8 if has_l1 else 7),
        out_specs=columnar.CompactGroups(*(spec,) * 7),
        check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _compact_merge_kernel(mesh: Mesh, padded_p: int, n_c: int, need_flags):
    """Folds n_c chunks of per-device compact group columns into the
    dense sharded accumulators inside ONE executable.

    Bit-parity contract with the legacy chunk loop: the legacy loop runs
    ``accs = accs + reduce_scatter(local_scatter(chunk c))`` chunk by
    chunk, so the merge must keep exactly that per-partition fold order —
    one local [padded_p] scatter (from the compact columns, so the input
    is max_groups entries, not row-scale) and one reduce-scatter per
    chunk, folded in chunk order. The collectives stay per chunk; the
    expensive row/group-scale partition passes are gone."""
    scatter_axes = _scatter_axes(mesh)
    needed = (True,) + tuple(bool(f) for f in need_flags)

    def local_step(accs, *flat):
        cols = list(accs)
        for c in range(n_c):
            chunk = flat[c * 6:(c + 1) * 6]
            cpk = chunk[0]
            for i in range(5):
                if not needed[i]:
                    continue
                partial = jnp.zeros((padded_p,), jnp.float32).at[cpk].add(
                    chunk[1 + i], mode="drop")
                cols[i] = cols[i] + _reduce_scatter(partial, scatter_axes)
        return columnar.PartitionAccumulators(*cols)

    spec = _spec(mesh)
    part = _part_spec(mesh)
    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(columnar.PartitionAccumulators(*(part,) * 5),)
        + (spec,) * (6 * n_c),
        out_specs=columnar.PartitionAccumulators(*(part,) * 5),
        check_vma=False)
    return jax.jit(fn)


def stream_bound_and_aggregate(mesh: Mesh,
                               key: jax.Array,
                               pid: np.ndarray,
                               pk: np.ndarray,
                               value,
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
                               value_transfer_dtype=None,
                               need_flags=(True, True, True, True),
                               has_group_clip: bool = True,
                               resilience=None,
                               resume_from=None,
                               compact_merge="auto",
                               segment_sort="auto"
                               ) -> columnar.PartitionAccumulators:
    """Chunked, transfer-overlapped multi-chip bound-and-aggregate.

    Rows are wire-codec-encoded into n_chunks x n_dev pid-disjoint
    buckets (one per device per chunk); each chunk ships as ONE sharded
    [n_dev, W] device_put whose async transfer overlaps the previous
    chunk's kernels — the mesh generalization of the single-device
    streaming pipeline (ops/streaming.py), with identical exactness
    (pid-disjoint buckets bound independently, accumulators add).
    Returns globally-sharded [padded_p] accumulators like
    bound_and_aggregate.

    resilience / resume_from: the runtime resilience bundle and explicit
    checkpoint hook, as on the single-device path (RESILIENCE.md). The
    mesh checkpoints per chunk; OOM degradation does not apply here (the
    chunk granularity is fixed by the mesh shape), so RESOURCE_EXHAUSTED
    re-issues the chunk like a transient fault.

    compact_merge: as on the single-device path — each chunk's devices
    emit compact per-group subtotal columns and ONE merge executable
    folds every chunk (per-chunk reduce-scatters preserved for bit
    parity, but the row/group-scale partition scatters are gone).
    "auto" (default) engages at >= streaming.COMPACT_MIN_PARTITIONS
    padded partitions; False restores the legacy per-chunk
    scatter+reduce-scatter loop.

    segment_sort: the bucketed segment-local sort inside each device's
    chunk kernel, as on the single-device path (streaming
    .stream_bound_and_aggregate) — "auto"/True/False resolve through the
    shared streaming.finish_wire_plan, so mesh and single-device runs of
    the same wire make the same tiling decision. BIT-identical released
    values either way.
    """
    import dataclasses

    from pipelinedp_tpu import profiler
    from pipelinedp_tpu.ops import streaming, wirecodec

    if resume_from is not None:
        if resilience is None:
            from pipelinedp_tpu import runtime as runtime_lib
            resilience = runtime_lib.StreamResilience()
        resilience = dataclasses.replace(resilience, resume_from=resume_from)
    n = len(pid)
    n_dev = mesh.devices.size
    padded_p = padded_num_partitions(mesh, num_partitions)
    pid = np.asarray(pid)
    if n == 0:
        return bound_and_aggregate(
            mesh, key, pid, pk, np.zeros(0, np.float32),
            np.zeros(0, bool), num_partitions=num_partitions,
            linf_cap=linf_cap, l0_cap=l0_cap, row_clip_lo=row_clip_lo,
            row_clip_hi=row_clip_hi, middle=middle,
            group_clip_lo=group_clip_lo, group_clip_hi=group_clip_hi,
            l1_cap=l1_cap, need_flags=need_flags,
            has_group_clip=has_group_clip)
    n_c = n_chunks or streaming._num_chunks(max(n // n_dev, 1))
    k = n_c * n_dev
    # Shared encode prologue with ops/streaming.py (pid-span validation,
    # width/bit planning, value plan, pid wire mode, native encoder).
    with profiler.stage("dp/wire_prep"):
        enc, info = wirecodec.make_encoder(
            pid, pk, value, num_partitions=num_partitions, k=k,
            value_transfer_dtype=value_transfer_dtype)
    if enc is not None:
        with enc:
            counts = enc.counts
            cap = wirecodec._round8(int(counts.max()))
            if info.pid_mode == wirecodec.PID_PLANES:
                # Arrival-order pid planes: no host sort at all.
                fmt = wirecodec.WireFormat(
                    bytes_pid=info.bytes_pid, bits_pk=info.bits_pk,
                    cap=cap, ucap=8, value=info.plan,
                    pid_mode=wirecodec.PID_PLANES, bits_pid=info.bits_pid)
                n_uniq = np.zeros(k, dtype=np.int64)

                def emit(c):
                    return enc.emit_range(c * n_dev, (c + 1) * n_dev, fmt)
            elif enc.entry_counts is not None:
                # Entry counts known at prep time: the per-bucket radix
                # sort joins the chunk pipeline (sort chunk c while chunk
                # c-1's sharded device_put + kernels are in flight).
                n_uniq = enc.entry_counts
                fmt = wirecodec.WireFormat(
                    bytes_pid=info.bytes_pid, bits_pk=info.bits_pk,
                    cap=cap,
                    ucap=wirecodec.round_ucap(int(n_uniq.max())),
                    value=info.plan)

                def emit(c):
                    b0, b1 = c * n_dev, (c + 1) * n_dev
                    sorted_uniq = enc.sort_range(b0, b1)
                    if not np.array_equal(sorted_uniq, n_uniq[b0:b1]):
                        # Same corrupted-input guard as the single-device
                        # slab loop (ops/streaming.py): analytic prep
                        # counts must equal the post-sort RLE counts.
                        raise RuntimeError(
                            "wirecodec: prep-time RLE entry counts "
                            "disagree with the sorted buckets")
                    return enc.emit_range(b0, b1, fmt)
            else:
                n_uniq = enc.sort_range(0, k)
                fmt = wirecodec.WireFormat(
                    bytes_pid=info.bytes_pid, bits_pk=info.bits_pk,
                    cap=cap,
                    ucap=wirecodec.round_ucap(int(n_uniq.max())),
                    value=info.plan)

                def emit(c):
                    return enc.emit_range(c * n_dev, (c + 1) * n_dev, fmt)

            # Tile geometry + int-accumulation gate, resolved exactly as
            # on the single-device path (tile fields are sort geometry,
            # not wire layout, so the emit closures above are unaffected
            # by the replace).
            fmt, int_clip = streaming.finish_wire_plan(
                fmt, segment_sort, info.max_run,
                num_partitions=padded_p, row_clip_lo=row_clip_lo,
                row_clip_hi=row_clip_hi, linf_cap=linf_cap,
                l1_mode=l1_cap is not None,
                group_clip_lo=group_clip_lo, group_clip_hi=group_clip_hi,
                need_flags=tuple(need_flags))
            return _drive_codec_chunks(mesh, key, emit, counts, n_uniq, fmt,
                                     n_c, n_dev, padded_p, linf_cap, l0_cap,
                                     row_clip_lo, row_clip_hi, middle,
                                     group_clip_lo, group_clip_hi, l1_cap,
                                     tuple(need_flags), has_group_clip,
                                     resilience,
                                     lambda: streaming._input_digest(
                                         pid, pk, value),
                                     compact_merge=compact_merge,
                                     int_clip=int_clip)
    slab, counts, n_uniq, fmt = wirecodec.encode_buckets_numpy(
        pid, pk, value, pid_lo=info.pid_lo, k=k, bytes_pid=info.bytes_pid,
        bits_pk=info.bits_pk, plan=info.plan, pid_mode=info.pid_mode,
        bits_pid=info.bits_pid)
    fmt, int_clip = streaming.finish_wire_plan(
        fmt, segment_sort, info.max_run,
        num_partitions=padded_p, row_clip_lo=row_clip_lo,
        row_clip_hi=row_clip_hi, linf_cap=linf_cap,
        l1_mode=l1_cap is not None,
        group_clip_lo=group_clip_lo, group_clip_hi=group_clip_hi,
        need_flags=tuple(need_flags))
    return _drive_codec_chunks(mesh, key,
                             lambda c: slab[c * n_dev:(c + 1) * n_dev],
                             counts, n_uniq, fmt, n_c,
                             n_dev, padded_p, linf_cap, l0_cap, row_clip_lo,
                             row_clip_hi, middle, group_clip_lo,
                             group_clip_hi, l1_cap, tuple(need_flags),
                             has_group_clip, resilience,
                             lambda: streaming._input_digest(pid, pk, value),
                             compact_merge=compact_merge,
                             int_clip=int_clip)


def replay_resident_wire(mesh: Mesh,
                         key: jax.Array,
                         wire,
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
                         segment_sort="auto",
                         compact_merge="auto",
                         resilience=None) -> columnar.PartitionAccumulators:
    """Answers one query from a mesh-ingested ResidentWire: the retained
    chunks ship sharded (one bucket per device) and fold through the
    same codec chunk kernels as the cold mesh stream — no encode and no
    host sort are re-paid. Bit-identical to
    stream_bound_and_aggregate(mesh, key, <source columns>,
    n_chunks=wire.n_chunks, ...) with the same knobs.
    """
    from pipelinedp_tpu import profiler
    from pipelinedp_tpu.ops import streaming

    n_dev = mesh.devices.size
    if wire.n_dev != n_dev:
        raise ValueError(
            f"handle was ingested for {wire.n_dev} devices; this mesh has "
            f"{n_dev}")
    padded_p = padded_num_partitions(mesh, wire.num_partitions)
    if wire.n_rows == 0:
        part_sharding = NamedSharding(mesh, _part_spec(mesh))
        return columnar.PartitionAccumulators(
            *(jax.device_put(np.zeros(padded_p, np.float32), part_sharding)
              for _ in range(5)))
    profiler.count_event(streaming.EVENT_SERVING_REPLAYS)
    from pipelinedp_tpu.obs import trace as obs_trace
    obs_trace.event("wire_replay", n_chunks=wire.n_chunks, n_dev=n_dev)
    fmt, int_clip = streaming.finish_wire_plan(
        wire.fmt, segment_sort, wire.max_run, num_partitions=padded_p,
        row_clip_lo=row_clip_lo, row_clip_hi=row_clip_hi,
        linf_cap=linf_cap, l1_mode=l1_cap is not None,
        group_clip_lo=group_clip_lo, group_clip_hi=group_clip_hi,
        need_flags=tuple(need_flags))
    return _drive_codec_chunks(
        mesh, key, lambda c: wire.slab[c * n_dev:(c + 1) * n_dev],
        wire.counts, wire.n_uniq, fmt, wire.n_chunks, n_dev, padded_p,
        linf_cap, l0_cap, row_clip_lo, row_clip_hi, middle, group_clip_lo,
        group_clip_hi, l1_cap, tuple(need_flags), has_group_clip,
        resilience, None, compact_merge=compact_merge, int_clip=int_clip)


def _reduce_scatter_lanes(x, scatter_axes):
    # Batched twin of _reduce_scatter: lane dim 0 is replicated, the
    # partition dim 1 scatters in the same ICI-first order.
    for axis in scatter_axes:
        x = jax.lax.psum_scatter(x, axis, scatter_dimension=1, tiled=True)
    return x


@functools.lru_cache(maxsize=None)
def _codec_batch_kernel(mesh: Mesh, padded_p: int, fmt, has_l1: bool,
                        need_flags, has_group_clip: bool):
    """Batched twin of _codec_scalar_kernel: ONE launch folds a chunk for
    B query configs. Each device decodes its codec bucket once, vmaps the
    bounding kernel over the per-config (key, caps, clip bounds) lanes,
    and reduce-scatters the [B, padded_p] partials along the partition
    dim. Per-config lanes match that config's sequential mesh replay: the
    per-device key schedule is the same _device_key(fold_in(key_b, c))
    and each lane's bounding math is independent."""
    from pipelinedp_tpu.ops import streaming

    axes = tuple(mesh.axis_names)
    scatter_axes = _scatter_axes(mesh)

    def local_step(keys, row, n_valid, n_uniq, linf_caps, l0_caps,
                   row_clip_los, row_clip_his, middles, group_clip_los,
                   group_clip_his, *l1_args):
        pid, pk, value, valid, vkw = streaming._decode_for_kernel(
            row[0], n_valid[0], n_uniq[0], fmt)

        def one(key, linf_cap, l0_cap, row_clip_lo, row_clip_hi, middle,
                group_clip_lo, group_clip_hi, l1_cap=None):
            return columnar.bound_and_aggregate(
                _device_key(key, axes), pid, pk, value, valid,
                num_partitions=padded_p,
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

        if has_l1:
            accs = jax.vmap(one)(keys, linf_caps, l0_caps, row_clip_los,
                                 row_clip_his, middles, group_clip_los,
                                 group_clip_his, l1_args[0])
        else:
            accs = jax.vmap(one)(keys, linf_caps, l0_caps, row_clip_los,
                                 row_clip_his, middles, group_clip_los,
                                 group_clip_his)
        return columnar.PartitionAccumulators(
            *(_reduce_scatter_lanes(a, scatter_axes) for a in accs))

    spec = _spec(mesh)
    lane_part = P(None, _scatter_axes(mesh))
    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), spec, spec, spec) + (P(),) * (8 if has_l1 else 7),
        out_specs=columnar.PartitionAccumulators(*(lane_part,) * 5),
        check_vma=False)
    return jax.jit(fn)


@jax.jit
def _fold_lane_keys(keys, c):
    # The engine's per-chunk key schedule, one lane per config.
    return jax.vmap(jax.random.fold_in, in_axes=(0, None))(keys, c)


def replay_resident_wire_batched(mesh: Mesh,
                                 keys,
                                 wire,
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
                                 has_group_clip: bool = True
                                 ) -> columnar.PartitionAccumulators:
    """Folds a mesh-ingested ResidentWire for B query configs in ONE
    launch per chunk — the multi-chip twin of
    streaming.replay_resident_wire_batched. Returns [B, padded_p]
    PartitionAccumulators sharded over the partition dim (lane dim
    replicated); lane b is bit-identical to that config's sequential
    replay_resident_wire(mesh, ...) fold, and therefore to its cold mesh
    run. Uses the parity-oracle statics (untiled packed sort, float32
    payload/accumulation, no hash bins), which the segment-sort parity
    matrix pins bit-identical to every other mode.
    """
    from pipelinedp_tpu import profiler
    from pipelinedp_tpu.ops import streaming

    import dataclasses

    n_dev = mesh.devices.size
    if wire.n_dev != n_dev:
        raise ValueError(
            f"handle was ingested for {wire.n_dev} devices; this mesh has "
            f"{n_dev}")
    padded_p = padded_num_partitions(mesh, wire.num_partitions)
    B = len(linf_caps)
    lane_sharding = NamedSharding(mesh, P(None, _scatter_axes(mesh)))
    if wire.n_rows == 0:
        return columnar.PartitionAccumulators(
            *(jax.device_put(np.zeros((B, padded_p), np.float32),
                             lane_sharding) for _ in range(5)))
    profiler.count_event(streaming.EVENT_SERVING_REPLAYS)
    from pipelinedp_tpu.obs import trace as obs_trace
    obs_trace.event("wire_replay_batched", n_chunks=wire.n_chunks,
                    n_dev=n_dev, width=B)
    fmt = dataclasses.replace(wire.fmt, tile_rows=0, tile_slack=0,
                              hash_bins=0, hash_bin_rows=0,
                              sort_value_narrow=False)
    kernel = _codec_batch_kernel(mesh, padded_p, fmt,
                                 l1_caps is not None, tuple(need_flags),
                                 has_group_clip)
    keys = jnp.stack([jnp.asarray(k) for k in keys])
    linf = jnp.asarray(np.asarray(linf_caps, dtype=np.int32))
    l0 = jnp.asarray(np.asarray(l0_caps, dtype=np.int32))
    rlo = jnp.asarray(np.asarray(row_clip_los, dtype=np.float32))
    rhi = jnp.asarray(np.asarray(row_clip_his, dtype=np.float32))
    mid = jnp.asarray(np.asarray(middles, dtype=np.float32))
    glo = jnp.asarray(np.asarray(group_clip_los, dtype=np.float32))
    ghi = jnp.asarray(np.asarray(group_clip_his, dtype=np.float32))
    l1 = (None if l1_caps is None
          else jnp.asarray(np.asarray(l1_caps, dtype=np.int32)))
    sharding = NamedSharding(mesh, _spec(mesh))
    counts = np.asarray(wire.counts, dtype=np.int32)
    n_uniq = np.asarray(wire.n_uniq, dtype=np.int32)
    kind_event = columnar.sampler_event(streaming.wire_sampler_kind(
        fmt, num_partitions=padded_p, l1_mode=l1 is not None))
    accs = None
    for c in range(wire.n_chunks):
        dslab = jax.device_put(wire.slab[c * n_dev:(c + 1) * n_dev],
                               sharding)
        dvalid = jax.device_put(counts[c * n_dev:(c + 1) * n_dev],
                                sharding)
        duniq = jax.device_put(n_uniq[c * n_dev:(c + 1) * n_dev], sharding)
        args = (_fold_lane_keys(keys, c), dslab, dvalid, duniq,
                linf, l0, rlo, rhi, mid, glo, ghi)
        if l1 is not None:
            args += (l1,)
        chunk_accs = kernel(*args)
        # First chunk's partials ARE the accumulators, exactly as
        # _MeshPlacement.step folds the sequential replay.
        accs = (chunk_accs if accs is None else
                columnar.PartitionAccumulators(
                    *(a + b for a, b in zip(accs, chunk_accs))))
        # ONE launch covers all B configs across n_dev bucket stages.
        profiler.count_event(streaming.EVENT_SERVING_LAUNCHES)
        profiler.count_event(kind_event)
    return accs


class _MeshPlacement(driver_lib.DevicePlacement):
    """Mesh strategy for the unified slab driver (runtime/driver.py owns
    the loop; this class owns how a chunk's sharded slab lands on the
    mesh and how chunk partials fold).

    One chunk per slab window: the chunk granularity is fixed by the
    mesh shape (n_dev codec buckets per chunk), so device OOM has no
    slab budget to degrade — it re-issues like a transient fault.
    Chunk accumulators are summed, never donated, so retrying a chunk
    can never read poisoned state.
    """

    stage_prefix = "dp/mesh_stream_chunk_"
    prefetch_prefix = "pdp-chunk-prefetch"
    degradable = False
    donates = False

    def __init__(self, *, transfer_fn, run_chunk, part_sharding, merge_fn,
                 compact, snapshot_fn):
        self._transfer_fn = transfer_fn
        self._run_chunk = run_chunk
        self._part_sharding = part_sharding
        self._merge_fn = merge_fn
        self._snapshot_fn = snapshot_fn
        self.compact = compact

    def init_state(self):
        # None until the first chunk: the first chunk's partials ARE the
        # accumulators (no zeros + add), exactly as the legacy loop.
        return None, None

    def transfer(self, slab, s0, s1):
        return self._transfer_fn(slab, s0)

    def step(self, c, payload, offset, accs, qhist):
        chunk_accs = self._run_chunk(c, payload)
        if accs is None:
            return chunk_accs, None
        return columnar.PartitionAccumulators(
            *(a + b for a, b in zip(accs, chunk_accs))), None

    def compact_step(self, c, payload, offset):
        return self._run_chunk(c, payload)

    def merge_pending(self, accs, pending):
        return self._merge_fn(accs, pending)

    def snapshot(self, accs, qhist):
        return self._snapshot_fn(accs, qhist)

    def restore(self, cp, expects_qhist):
        accs = columnar.PartitionAccumulators(
            *(jax.device_put(np.array(a), self._part_sharding)
              for a in cp.accs))
        return accs, None

def _drive_codec_chunks(mesh, key, emit, counts, n_uniq, fmt, n_c, n_dev,
                        padded_p, linf_cap, l0_cap, row_clip_lo,
                        row_clip_hi, middle, group_clip_lo, group_clip_hi,
                        l1_cap, need_flags, has_group_clip, resilience=None,
                        data_digest_fn=None, compact_merge: bool = True,
                        int_clip=None):
    """Runs the mesh chunk schedule on the unified slab driver
    (runtime.SlabDriver — the same loop body as the single-device path,
    so checkpoint/resume, retry, prefetch, compact merge, fault
    injection and the dispatch watchdog are shared, not twinned).

    Each chunk is one slab window: ``emit(c)`` is the pure host encode
    (prefetchable, discardable), the transfer ships the chunk's sharded
    [n_dev, W] slab plus its count/entry-count rows, and the chunk
    kernel folds the reduce-scattered partials into the running sharded
    accumulators. In compact-merge mode per-device compact group columns
    collect per chunk and fold into the dense sharded accumulators only
    at checkpoints and once at the end (_compact_merge_kernel, which
    keeps the legacy per-partition fold order for bit parity)."""
    from pipelinedp_tpu import profiler
    from pipelinedp_tpu.ops import streaming

    import dataclasses

    max_groups = None
    if (streaming._compact_enabled(compact_merge, padded_p)
            and fmt.pid_sorted):
        max_groups = columnar.compact_group_bound(fmt.cap, fmt.ucap,
                                                  l0_cap)
    compact = max_groups is not None
    # Plain-int pair so the lru_cached kernel builders key on it.
    int_clip_key = (None if int_clip is None
                    else (int(int_clip[0]), int(int_clip[1])))

    def build_kernel(f):
        if compact:
            return _codec_compact_kernel(mesh, padded_p, f, max_groups,
                                         l1_cap is not None, need_flags,
                                         has_group_clip, int_clip_key)
        return _codec_scalar_kernel(mesh, padded_p, f,
                                    l1_cap is not None, need_flags,
                                    has_group_clip, int_clip_key)

    kernel = build_kernel(fmt)
    # Per-chunk demotion target of the hash-binned group stage: a chunk
    # whose RLE entry count exceeds the static bin count runs the tiled
    # kernel (built lazily on first demotion; decided on host counts
    # that ride the wire fingerprint, so replays/resumes demote
    # identically).
    hash_on = fmt.hash_bins > 0 and fmt.pid_sorted
    fmt_demoted = (dataclasses.replace(fmt, hash_bins=0, hash_bin_rows=0)
                   if hash_on else fmt)
    scatter_passes = 1 + sum(bool(f) for f in need_flags)
    sharding = NamedSharding(mesh, _spec(mesh))
    part_sharding = NamedSharding(mesh, _part_spec(mesh))
    counts = np.asarray(counts, dtype=np.int32)
    n_uniq = np.asarray(n_uniq, dtype=np.int32)

    # The sampler counters count per LAUNCH (one chunk = one kernel over
    # n_dev buckets), like the demotion counter.
    l1_mode = l1_cap is not None
    kind = streaming.wire_sampler_kind(fmt, num_partitions=padded_p,
                                       l1_mode=l1_mode)
    kind_demoted = streaming.wire_sampler_kind(
        fmt_demoted, num_partitions=padded_p, l1_mode=l1_mode)
    cells = max(fmt.hash_bins * fmt.hash_bin_rows * n_dev, 1)

    def transfer_chunk(slab, c):
        dslab = jax.device_put(slab, sharding)
        dvalid = jax.device_put(counts[c * n_dev:(c + 1) * n_dev],
                                sharding)
        duniq = jax.device_put(n_uniq[c * n_dev:(c + 1) * n_dev],
                               sharding)
        return dslab, dvalid, duniq

    def run_chunk(c, payload):
        dslab, dvalid, duniq = payload
        if (hash_on and int(n_uniq[c * n_dev:(c + 1) * n_dev].max())
                > fmt.hash_bins):
            profiler.count_event(columnar.EVENT_HASH_DEMOTIONS)
            profiler.count_event(columnar.sampler_event(kind_demoted))
            use_kernel = build_kernel(fmt_demoted)
        else:
            profiler.count_event(columnar.sampler_event(kind))
            if kind == "hash":
                rows = int(counts[c * n_dev:(c + 1) * n_dev].sum())
                profiler.count_event(columnar.EVENT_HASH_OCCUPANCY,
                                     min(100, (100 * rows) // cells))
            use_kernel = kernel
        args = (jax.random.fold_in(key, c), dslab, dvalid, duniq,
                linf_cap, l0_cap, float(row_clip_lo), float(row_clip_hi),
                float(middle), float(group_clip_lo), float(group_clip_hi))
        if l1_cap is not None:
            args += (l1_cap,)
        return use_kernel(*args)

    def merge_pending(accs, pending):
        if accs is None:
            accs = columnar.PartitionAccumulators(
                *(jax.device_put(np.zeros(padded_p, np.float32),
                                 part_sharding) for _ in range(5)))
        max_kept = int(jax.device_get(jnp.max(
            jnp.concatenate([p.n_kept for p in pending]))))
        if max_kept > max_groups:
            raise RuntimeError(
                f"compact merge: a chunk kept {max_kept} groups, above "
                f"the static bound {max_groups} — the pid-sorted wire "
                f"contract was violated; refusing to release truncated "
                f"accumulators")
        profiler.count_event(streaming.EVENT_COMPACT_MERGE_SCATTERS,
                             scatter_passes * len(pending))
        merge = _compact_merge_kernel(mesh, padded_p, len(pending),
                                      tuple(need_flags))
        flat = [a for p in pending for a in p[:6]]
        return merge(accs, *flat)

    placement = _MeshPlacement(
        transfer_fn=transfer_chunk, run_chunk=run_chunk,
        part_sharding=part_sharding, merge_fn=merge_pending,
        compact=compact, snapshot_fn=streaming._snapshot_host)
    plan = driver_lib.SlabPlan(
        n_chunks=n_c,
        window_chunks=1,  # chunk granularity is fixed by the mesh shape
        fmt_desc=repr(("mesh", n_dev, fmt)),
        counts=counts,
        n_uniq=n_uniq,
        buckets_per_chunk=n_dev,
        scatter_passes=scatter_passes,
        quantile=False,
        data_digest_fn=data_digest_fn,
        prefetch_depth=streaming.prefetch_depth())
    accs, _ = driver_lib.SlabDriver(
        placement, plan, lambda s0, s1: emit(s0), key, resilience).run()
    return accs


def bound_and_aggregate_vector(mesh: Mesh,
                               key: jax.Array,
                               pid: np.ndarray,
                               pk: np.ndarray,
                               value: np.ndarray,
                               valid: np.ndarray,
                               *,
                               num_partitions: int,
                               linf_cap,
                               l0_cap,
                               max_norm,
                               norm_ord: int,
                               l1_cap=None,
                               pid_sorted: bool = False,
                               max_segments=None):
    """Multi-chip VECTOR_SUM path; see bound_and_aggregate.

    pid_sorted: the caller staged rows pre-sorted by pid (host argsort
    before stage_rows — the stable shard partition keeps every shard's
    block pid-sorted), so each device runs the packed 3-key bounding
    sort instead of the general 4-key one; max_segments bounds any one
    shard's distinct pids."""
    padded_p = padded_num_partitions(mesh, num_partitions)
    dpid, dpk, dval, dvalid = _shard_and_put(mesh, pid, pk, value, valid)
    kernel = _vector_kernel(mesh, padded_p, norm_ord,
                            has_l1=l1_cap is not None,
                            pid_sorted=pid_sorted,
                            max_segments=max_segments)
    args = (key, dpid, dpk, dval, dvalid, linf_cap, l0_cap, float(max_norm))
    if l1_cap is not None:
        args += (l1_cap,)
    return kernel(*args)


def build_finalize_epilogue(mesh: Mesh, plan):
    """Mesh variant of the fused finalization epilogue (ops/finalize.py).

    The accumulators arrive sharded over the partition dimension (the
    reduce-scatter layout, _part_spec); the whole epilogue — selection,
    batched noise, metric math, thresholding — compiles as one executable
    under XLA's SPMD partitioner, with explicit sharding constraints
    pinning every released column to the partition layout so no
    all-gather sneaks onto the serving path before the single batched
    device→host transfer.

    Deliberately NOT a per-device-key shard_map: the PRNG draws must stay
    *globally* keyed so mesh and single-device runs of the same seed
    release identical noise (the bit-parity contract pinned by
    tests/finalize_test.py). Elementwise ops over [padded_p] arrays
    partition perfectly under SPMD anyway — shard_map would buy nothing
    but a different (per-shard) noise stream.
    """
    from pipelinedp_tpu.ops import finalize as finalize_ops

    part = NamedSharding(mesh, _part_spec(mesh))

    def body(op):
        columns, keep = finalize_ops.epilogue_body(plan, op)
        columns = {
            name: jax.lax.with_sharding_constraint(col, part)
            for name, col in columns.items()
        }
        return columns, jax.lax.with_sharding_constraint(keep, part)

    return jax.jit(body)
