"""Batched per-partition DP quantile trees for the columnar engine.

The reference computes PERCENTILE with one C++ QuantileTree object per
partition, built row by row and noised node by node during the quantile
walk (combiners.py:590-669 via PyDP). The TPU-native formulation builds
EVERY partition's tree at once: the leaf level is a single
[num_partitions, branching**height] histogram produced by one segment-sum
over the (already contribution-bounded) rows, upper levels are reshape-sums
of the leaf level, and each level gets one batched noise call. The quantile
walk is then pure post-processing of DP-released node counts — no privacy
left in it — so it runs as vectorized numpy over all partitions and all
requested quantiles at once.

Budget semantics match pipelinedp_tpu/quantile_tree.py (the host twin, and
through it the PyDP algorithm): eps/delta split evenly across tree levels;
per-level noise uses L1 sensitivity l0*linf (Laplace) or L2 sensitivity
sqrt(l0)*linf (Gaussian), since each contribution increments exactly one
node per level.

Sampling-replay contract: the per-row keep mask feeding leaf_histograms
comes from columnar.bound_row_mask called with the SAME key and the SAME
sort statics as the aggregation kernel of the run — including the
pid_sorted/max_segments flags and, since round 9, the tile_rows/tile_slack
geometry of the bucketed segment-local sort (streaming
._chunk_step_rle_quantile plumbs all four from the chunk's WireFormat).
The packed 3-key sort is where the sampling randomness lives, so any
divergence in sort configuration between the two kernels would silently
de-correlate "rows kept for COUNT/SUM" from "rows kept for PERCENTILE" of
one release. The tiled and global packed sorts are bit-identical by
construction (ops/columnar._sample_rows_and_groups_tiled), which is what
lets the segment_sort knob pick either geometry without touching the
replayed masks.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from pipelinedp_tpu import noise_core
from pipelinedp_tpu.ops import noise as noise_ops

# Dense [num_partitions, leaves] budget (2^28 floats = 1 GiB). Larger
# partition counts are processed in partition blocks of this many elements:
# rows are sorted by partition once, each block slices its row range and
# histograms into a [block, leaves] array — same released values, bounded
# memory.
MAX_HISTOGRAM_ELEMENTS = 2**28


def blocked_quantile_columns(spk: jnp.ndarray, sval: jnp.ndarray,
                             skeep: jnp.ndarray, row_bounds: np.ndarray, *,
                             num_partitions: int, num_leaves: int, lower,
                             upper, num_quantiles: int, finish_fn
                             ) -> np.ndarray:
    """[num_partitions, n_quantiles] DP quantiles, block by block.

    spk/sval/skeep: device arrays sorted by partition id (skeep is the
    contribution-bounding row mask, already permuted); row_bounds[p] is the
    host-side row offset of partition p in the sorted order. finish_fn
    turns one [block, num_leaves] histogram (device array) into the
    block's [block, n_quantiles] DP quantiles — noise + tree walk in
    whichever mode the engine runs (the eps/delta split is per tree, so
    per-block noising is identical to one global call: blocks partition
    the node space).
    """
    block_p = max(1, MAX_HISTOGRAM_ELEMENTS // num_leaves)
    starts = list(range(0, num_partitions, block_p))
    n_rows = int(spk.shape[0])
    out = np.zeros((num_partitions, num_quantiles), dtype=np.float64)
    for p0 in starts:
        p1 = min(p0 + block_p, num_partitions)
        rows_b = int(row_bounds[p1] - row_bounds[p0])
        if rows_b == 0 or n_rows == 0:
            # No contributions: zero trees (noise in finish_fn may still
            # release nonzero counts — same as dense on empty partitions).
            hist = jnp.zeros((block_p, num_leaves), dtype=jnp.float32)
        else:
            # Slice size = rows rounded up to a power of two, so skewed
            # blocks cost work proportional to their own rows while the
            # kernel compiles at most log2(n) shapes. The start clamp near
            # the array end is harmless: the in-block partition mask drops
            # neighbouring rows the padded window picks up.
            # rows_b <= n_rows, so the clamp never shrinks below rows_b.
            size = 1 << (rows_b - 1).bit_length()
            size = min(max(size, 1024), n_rows)
            start = min(int(row_bounds[p0]), n_rows - size)
            bpk = jax.lax.dynamic_slice_in_dim(spk, start, size)
            bval = jax.lax.dynamic_slice_in_dim(sval, start, size)
            bkeep = jax.lax.dynamic_slice_in_dim(skeep, start, size)
            weights = bkeep & (bpk >= p0) & (bpk < p1)
            hist = leaf_histograms(bpk - p0, bval, weights,
                                   num_partitions=block_p,
                                   num_leaves=num_leaves,
                                   lower=lower,
                                   upper=upper)
        # Full [block_p, leaves] shape even for the tail block, so the
        # noise/walk kernels compile once; only the output is trimmed.
        # (The extra padding partitions burn a little noise, not budget —
        # noise is per released node, and padding nodes are discarded.)
        out[p0:p1] = finish_fn(hist)[:p1 - p0]
    return out


@functools.partial(jax.jit, static_argnames=("num_partitions", "num_leaves"))
def leaf_histograms(pk: jnp.ndarray, value: jnp.ndarray,
                    weights: jnp.ndarray, *, num_partitions: int,
                    num_leaves: int, lower, upper) -> jnp.ndarray:
    """[num_partitions, num_leaves] leaf counts of every partition's tree.

    ``weights`` is the per-row keep mask from contribution bounding
    (columnar.bound_row_mask); lower/upper are runtime scalars.
    """
    frac = (jnp.clip(value, lower, upper) - lower) / (upper - lower)
    leaf = jnp.minimum((frac * num_leaves).astype(jnp.int32), num_leaves - 1)
    seg = pk * num_leaves + leaf
    counts = jax.ops.segment_sum(weights.astype(jnp.float32), seg,
                                 num_segments=num_partitions * num_leaves)
    return counts.reshape(num_partitions, num_leaves)


def level_counts(leaf_hist: np.ndarray, branching: int,
                 height: int) -> List[np.ndarray]:
    """Per-level node counts derived from the leaf level by reshape-sums.

    Level l (0-based, children of the root first) has branching**(l+1)
    nodes per partition — same convention as QuantileTree._level_counts.
    """
    num_partitions = leaf_hist.shape[0]
    levels = []
    for level in range(height):
        nodes = branching**(level + 1)
        levels.append(
            leaf_hist.reshape(num_partitions, nodes, -1).sum(axis=2))
    return levels


def _walk_impl(xp, noised_levels, quantiles_arr, lower, upper,
               branching: int, dtype, tiny):
    """The tree descent, shared by the host and device walks (xp = numpy
    or jax.numpy): descend level by level following the target rank;
    partitions whose subtree total drops to <= 0 resolve to the middle of
    their current range. Vectorized twin of
    QuantileTree._locate_quantile."""
    b = branching
    num_partitions = noised_levels[0].shape[0]
    num_q = quantiles_arr.shape[0]
    node = xp.zeros((num_partitions, num_q), dtype=xp.int32)
    lo = xp.full((num_partitions, num_q), lower, dtype=dtype)
    hi = xp.full((num_partitions, num_q), upper, dtype=dtype)
    target = xp.tile(quantiles_arr.astype(dtype), (num_partitions, 1))
    dead = xp.zeros((num_partitions, num_q), dtype=bool)
    dead_result = xp.zeros((num_partitions, num_q), dtype=dtype)

    for level_nodes in noised_levels:
        lvl = xp.maximum(level_nodes.astype(dtype), 0.0)
        idx = node[:, :, None] * b + xp.arange(b, dtype=xp.int32)  # [P,Q,b]
        children = xp.take_along_axis(lvl[:, None, :], idx, axis=2)
        total = children.sum(axis=2)
        newly_dead = ~dead & (total <= 0)
        dead_result = xp.where(newly_dead, lo + (hi - lo) / 2, dead_result)
        dead = dead | newly_dead
        cum = xp.cumsum(children, axis=2)
        rank = target * total
        # searchsorted(cum, rank, side="right"), clipped to the last child.
        child = xp.minimum((cum <= rank[:, :, None]).sum(axis=2), b - 1)
        child_count = xp.take_along_axis(children, child[:, :, None],
                                         axis=2)[:, :, 0]
        below = xp.take_along_axis(cum, child[:, :, None],
                                   axis=2)[:, :, 0] - child_count
        target = xp.where(child_count > 0,
                          (rank - below) / xp.maximum(child_count, tiny),
                          0.5)
        target = xp.clip(target, 0.0, 1.0)
        width = (hi - lo) / b
        lo = lo + child * width
        hi = lo + width
        node = node * b + child
    out = lo + target * (hi - lo)
    return xp.where(dead, dead_result, out)


def walk_quantiles(noised_levels: Sequence[np.ndarray],
                   quantiles: Sequence[float], lower: float, upper: float,
                   branching: int) -> np.ndarray:
    """[num_partitions, num_quantiles] quantile estimates (host, float64)."""
    levels = [np.asarray(lvl, dtype=np.float64) for lvl in noised_levels]
    return _walk_impl(np, levels, np.asarray(quantiles, dtype=np.float64),
                      lower, upper, branching, np.float64, 1e-300)


@functools.partial(jax.jit, static_argnames=("branching",))
def walk_quantiles_device(noised_levels, quantiles_arr: jnp.ndarray,
                          lower, upper, *, branching: int) -> jnp.ndarray:
    """Device twin of walk_quantiles (same _walk_impl descent, jnp ops,
    float32) so the O(partitions x nodes) noised levels never leave the
    device — only the [partitions, quantiles] result does."""
    return _walk_impl(jnp, noised_levels, quantiles_arr, lower, upper,
                      branching, jnp.float32, 1e-30)


def noised_levels_host(levels: Sequence[np.ndarray], eps: float, delta: float,
                       l0: int, linf: float,
                       is_gaussian: bool) -> List[np.ndarray]:
    """Secure host noise per level (float64, granularity-snapped sampler) —
    identical budget math to QuantileTree._noise_counts."""
    height = len(levels)
    eps_l, delta_l = eps / height, delta / height
    out = []
    for counts in levels:
        counts = np.asarray(counts, dtype=np.float64)
        if is_gaussian:
            sigma = noise_core.analytic_gaussian_sigma(
                eps_l, delta_l, np.sqrt(l0) * linf)
            out.append(counts + noise_core.sample_gaussian(
                sigma, counts.shape))
        else:
            scale = noise_core.laplace_diversity(eps_l, l0 * linf)
            out.append(counts + noise_core.sample_laplace(
                scale, counts.shape))
    return out


def noised_levels_device(key: jax.Array, levels: Sequence[jnp.ndarray],
                         eps: float, delta: float, l0: int, linf: float,
                         is_gaussian: bool) -> List[jnp.ndarray]:
    """Device-side batched noise per level (fast mode). Returns device
    arrays — feed them to walk_quantiles_device so the O(partitions x
    nodes) level counts never cross the host link."""
    height = len(levels)
    eps_l, delta_l = eps / height, delta / height
    if is_gaussian:
        sigma = noise_core.analytic_gaussian_sigma(eps_l, delta_l,
                                                   np.sqrt(l0) * linf)
        gran = noise_core.gaussian_granularity(sigma)
    else:
        scale = noise_core.laplace_diversity(eps_l, l0 * linf)
        gran = noise_core.laplace_granularity(scale)
    out = []
    for i, counts in enumerate(levels):
        k = jax.random.fold_in(key, i)
        if is_gaussian:
            out.append(
                noise_ops.add_gaussian_noise(k, jnp.asarray(counts), sigma,
                                             gran))
        else:
            out.append(
                noise_ops.add_laplace_noise(k, jnp.asarray(counts), scale,
                                            gran))
    return out
