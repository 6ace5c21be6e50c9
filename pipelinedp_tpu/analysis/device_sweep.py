"""Device-side (jitted JAX) multi-parameter utility-analysis sweep.

The reference evaluates a parameter sweep by building n_configurations
deep-copied combiner graphs and running every one of them against every row
(analysis/utility_analysis_engine.py:99-143). The host rewrite already
collapsed that to numpy grids (per_partition.py); this module puts the same
error model on the accelerator and keeps it there:

  * per-group metric values broadcast against a leading configuration axis,
    every [n_configs, n_partitions] error grid produced by batched
    segment-sums inside jit;
  * the cross-partition report reduction (cross_partition._metric_utility's
    weighted sums, including the per-partition nonlinearities rmse and
    relative errors) runs as a second device kernel over partition-size
    buckets, so a full UtilityReport sweep pulls only
    [n_buckets, n_fields, n_configs] scalars off the device — the
    [n_configs, n_partitions] grids are materialized to host numpy lazily
    and only if a consumer actually reads them.

The numpy implementation in per_partition.py / cross_partition.py remains
the conformance oracle; tests/analysis_test.py pins the two paths against
each other.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import numpy as np

# [config-chunk, n_groups] float intermediates are bounded to roughly this
# many elements (the stacked segment-sum operand peaks at ~4x this, i.e.
# ~2 GB of f32 at this setting — well inside one v5e chip's HBM) so a
# wide sweep over tens of millions of groups never overflows device
# memory; configurations beyond the chunk run in further launches of the
# same compiled kernel. Sized so the 64-config benchmark sweep over 2M
# groups is ONE launch per metric: every extra launch pays a dispatch
# round trip.
_CHUNK_ELEMENT_BUDGET = 1 << 27

# Order of the per-(config, bucket) report sums produced by _report_kernel.
# ABS/REL blocks mirror cross_partition._metric_utility's ValueErrors
# fields; the DROP block mirrors its DataDropInfo attribution.
ABS_FIELDS = ("exp_l0", "var_l0", "clip_min", "clip_max", "bias", "variance",
              "rmse", "rmse_dropped")
N_ABS = len(ABS_FIELDS)
N_REPORT_FIELDS = 2 * N_ABS + 4  # abs + rel + (raw, l0, linf, selection)

def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def should_use_device(num_groups: int, n_configs: int) -> bool:
    """Auto-dispatch policy: accelerate when an accelerator exists and the
    grid is big enough to amortize the launch. A device sweep that then
    fails raises; it never reruns quietly on the host."""
    jax, _ = _jnp()
    if jax.default_backend() == "cpu":
        return False
    return num_groups * max(n_configs, 1) >= (1 << 16)


@functools.lru_cache(maxsize=None)
def _kernels():
    """Builds the jitted kernels lazily so that importing the analysis
    package never initializes a JAX backend."""
    jax, jnp = _jnp()

    @functools.partial(jax.jit,
                       static_argnames=("n_partitions", "metric_kinds"))
    def metric_grids_multi(counts, sums, pk_ids, npart, lo, hi, l0,
                           n_partitions, metric_kinds):
        """All metrics' error grids in ONE dispatch.

        lo/hi: [n_metrics, C] per-metric clip bounds; l0: [C] (shared
        across metrics, so the keep-probability ratio q is computed
        once). Returns a tuple of (raw [P], grids [4, C, P]) per metric.
        Every launch saved is a dispatch round trip.
        """
        q = jnp.minimum(1.0, l0[:, None] / jnp.maximum(npart, 1.0)[None, :])
        outs = []
        for m, kind in enumerate(metric_kinds):
            if kind == "sum":
                v = sums
            elif kind == "count":
                v = counts
            else:  # privacy_id_count
                v = (counts > 0).astype(counts.dtype)
            vb = v[None, :]
            x = jnp.clip(vb, lo[m][:, None], hi[m][:, None])
            err = x - vb
            below = jnp.where(vb < lo[m][:, None], err, 0.0)
            above = jnp.where(vb > hi[m][:, None], err, 0.0)
            data = jnp.stack(
                [below, above, -x * (1.0 - q), x * x * q * (1.0 - q)])
            grids = jax.ops.segment_sum(jnp.moveaxis(data, -1, 0),
                                        pk_ids,
                                        num_segments=n_partitions)
            raw = jax.ops.segment_sum(v, pk_ids,
                                      num_segments=n_partitions)
            outs.append((raw, jnp.moveaxis(grids, 0, -1)))
        return tuple(outs)

    @functools.partial(jax.jit, static_argnames=("n_partitions",))
    def moment_grids(pk_ids, npart, l0, n_partitions):
        """[3, C, P] Poisson-binomial moment grids (mean, var, third
        central moment of the partition's surviving-unit count) feeding the
        refined-normal keep-probability approximation."""
        q = jnp.minimum(1.0, l0[:, None] / jnp.maximum(npart, 1.0)[None, :])
        data = jnp.stack([q, q * (1.0 - q), q * (1.0 - q) * (1.0 - 2.0 * q)])
        sums = jax.ops.segment_sum(jnp.moveaxis(data, -1, 0),
                                   pk_ids,
                                   num_segments=n_partitions)
        return jnp.moveaxis(sums, 0, -1)

    @functools.partial(jax.jit, static_argnames=("n_buckets",))
    def report_sums(raw, grids, std_noise, keep, bucket_ids, n_buckets):
        """[B, N_REPORT_FIELDS, C] cross-partition sums for one metric.

        Device twin of cross_partition._metric_utility's reductions: the
        per-partition nonlinearities (rmse, relative division by raw,
        dropped-mass attribution) are evaluated on-device and summed per
        partition-size bucket; the host divides by the weights and fills
        dataclasses. keep is [C, P] (ones for public partitions).
        """
        clip_min, clip_max, exp_l0, var_l0 = (grids[0], grids[1], grids[2],
                                              grids[3])
        rawb = jnp.broadcast_to(raw[None, :], exp_l0.shape)
        bias = exp_l0 + clip_min + clip_max
        variance = var_l0 + (std_noise * std_noise)[:, None]
        rmse = jnp.sqrt(bias * bias + variance)
        rmse_dropped = keep * rmse + (1.0 - keep) * jnp.abs(rawb)
        safe_raw = jnp.where(rawb == 0.0, 1.0, rawb)
        nz = (rawb != 0.0).astype(rmse.dtype)
        inv = nz / safe_raw
        inv2 = nz / (safe_raw * safe_raw)
        abs_fields = (exp_l0, var_l0, clip_min, clip_max, bias, variance,
                      rmse, rmse_dropped)
        rel_fields = (exp_l0 * inv, var_l0 * inv2, clip_min * inv,
                      clip_max * inv, bias * inv, variance * inv2,
                      rmse * inv, rmse_dropped * inv)
        l0_dropped = -exp_l0
        linf_dropped = clip_min - clip_max
        selection_dropped = (rawb - l0_dropped - linf_dropped) * (1.0 - keep)
        data = jnp.stack(
            [f * keep for f in abs_fields + rel_fields] +
            [rawb, l0_dropped, linf_dropped, selection_dropped])
        return jax.ops.segment_sum(jnp.moveaxis(data, -1, 0),
                                   bucket_ids,
                                   num_segments=n_buckets)

    @functools.partial(jax.jit, static_argnames=("n_buckets",))
    def keep_sums(keep, bucket_ids, n_buckets):
        """[B, 2, C]: (sum keep, sum keep*(1-keep)) per bucket — the
        kept-partitions Poisson-binomial mean/variance."""
        data = jnp.stack([keep, keep * (1.0 - keep)])
        return jax.ops.segment_sum(jnp.moveaxis(data, -1, 0),
                                   bucket_ids,
                                   num_segments=n_buckets)

    return moment_grids, report_sums, keep_sums, metric_grids_multi


# ---------------------------------------------------------------------------
# Mesh (multi-chip) kernels: the same math shard_map'ed over the device
# mesh. Groups shard over all mesh axes; the per-partition segment-sums
# produce full-width partials that ride the same ICI-first reduce-scatter
# as the aggregation kernels (parallel/sharded.py), leaving every grid
# sharded over the partition dimension. The report reduction then runs
# shard-local and psums its small [B, F, C] output.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _mesh_metric_kernel(mesh, padded_p: int, metric_kind: str):
    jax, jnp = _jnp()
    from jax.sharding import PartitionSpec as P
    from pipelinedp_tpu.parallel import sharded

    scatter_axes = sharded._scatter_axes(mesh)

    def local_step(counts, sums, pk_ids, npart, lo, hi, l0):
        if metric_kind == "sum":
            v = sums
        elif metric_kind == "count":
            v = counts
        else:  # privacy_id_count
            v = (counts > 0).astype(counts.dtype)
        vb = v[None, :]
        q = jnp.minimum(1.0, l0[:, None] / jnp.maximum(npart, 1.0)[None, :])
        x = jnp.clip(vb, lo[:, None], hi[:, None])
        err = x - vb
        below = jnp.where(vb < lo[:, None], err, 0.0)
        above = jnp.where(vb > hi[:, None], err, 0.0)
        data = jnp.stack(
            [below, above, -x * (1.0 - q), x * x * q * (1.0 - q)])
        # [P, 4, C] partials; padding groups carry pk == padded_p and drop.
        grids = jax.ops.segment_sum(jnp.moveaxis(data, -1, 0), pk_ids,
                                    num_segments=padded_p)
        raw = jax.ops.segment_sum(v, pk_ids, num_segments=padded_p)
        return (sharded._reduce_scatter(raw, scatter_axes),
                sharded._reduce_scatter(grids, scatter_axes))

    fn = jax.shard_map(local_step,
                       mesh=mesh,
                       in_specs=(sharded._spec(mesh),) * 4 + (P(),) * 3,
                       out_specs=(sharded._part_spec(mesh),) * 2,
                       check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _mesh_moment_kernel(mesh, padded_p: int):
    jax, jnp = _jnp()
    from jax.sharding import PartitionSpec as P
    from pipelinedp_tpu.parallel import sharded

    scatter_axes = sharded._scatter_axes(mesh)

    def local_step(pk_ids, npart, l0):
        q = jnp.minimum(1.0, l0[:, None] / jnp.maximum(npart, 1.0)[None, :])
        data = jnp.stack([q, q * (1.0 - q), q * (1.0 - q) * (1.0 - 2.0 * q)])
        sums = jax.ops.segment_sum(jnp.moveaxis(data, -1, 0), pk_ids,
                                   num_segments=padded_p)  # [P, 3, C]
        return sharded._reduce_scatter(sums, scatter_axes)

    fn = jax.shard_map(local_step,
                       mesh=mesh,
                       in_specs=(sharded._spec(mesh),) * 2 + (P(),),
                       out_specs=sharded._part_spec(mesh),
                       check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _mesh_report_kernel(mesh, n_buckets_p1: int, with_keep_sums: bool):
    jax, jnp = _jnp()
    from jax.sharding import PartitionSpec as P
    from pipelinedp_tpu.parallel import sharded

    all_axes = tuple(mesh.axis_names)

    def local_step(raw, grids, std_noise, keep, bucket_ids):
        # Shard-local layout: raw [P_l], grids [P_l, 4, C], keep [P_l, C]
        # (pre-transposed on host), bucket_ids [P_l]. Same field math as
        # the single-device report_sums, partition-major.
        clip_min, clip_max = grids[:, 0], grids[:, 1]
        exp_l0, var_l0 = grids[:, 2], grids[:, 3]
        rawb = jnp.broadcast_to(raw[:, None], exp_l0.shape)
        bias = exp_l0 + clip_min + clip_max
        variance = var_l0 + (std_noise * std_noise)[None, :]
        rmse = jnp.sqrt(bias * bias + variance)
        rmse_dropped = keep * rmse + (1.0 - keep) * jnp.abs(rawb)
        safe_raw = jnp.where(rawb == 0.0, 1.0, rawb)
        nz = (rawb != 0.0).astype(rmse.dtype)
        inv = nz / safe_raw
        inv2 = nz / (safe_raw * safe_raw)
        abs_fields = (exp_l0, var_l0, clip_min, clip_max, bias, variance,
                      rmse, rmse_dropped)
        rel_fields = (exp_l0 * inv, var_l0 * inv2, clip_min * inv,
                      clip_max * inv, bias * inv, variance * inv2,
                      rmse * inv, rmse_dropped * inv)
        l0_dropped = -exp_l0
        linf_dropped = clip_min - clip_max
        selection_dropped = (rawb - l0_dropped - linf_dropped) * (1.0 - keep)
        data = jnp.stack(
            [f * keep for f in abs_fields + rel_fields] +
            [rawb, l0_dropped, linf_dropped, selection_dropped])  # [F, P, C]
        sums = jax.ops.segment_sum(jnp.moveaxis(data, 1, 0), bucket_ids,
                                   num_segments=n_buckets_p1)
        for axis in all_axes:
            sums = jax.lax.psum(sums, axis)
        if not with_keep_sums:
            return sums
        kdata = jnp.stack([keep, keep * (1.0 - keep)])  # [2, P, C]
        ksums = jax.ops.segment_sum(jnp.moveaxis(kdata, 1, 0), bucket_ids,
                                    num_segments=n_buckets_p1)
        for axis in all_axes:
            ksums = jax.lax.psum(ksums, axis)
        return sums, ksums

    part = sharded._part_spec(mesh)
    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(part, part, P(), part, part),
        out_specs=(P(), P()) if with_keep_sums else P(),
        check_vma=False)
    return jax.jit(fn)


@dataclasses.dataclass
class _MetricGrids:
    """Device-resident error grids of one metric."""
    raw: object  # [P] device array
    grids: object  # [4, C, P] device array
    std_noise: np.ndarray  # [C] host
    metric_kind: str


class DeviceSweep:
    """Device-resident state of one utility-analysis sweep.

    Uploads the pre-aggregate columns once, computes per-metric error grids
    (kept on device), and serves both consumers: lazy host materialization
    of the [C, P] grids and the fused cross-partition report reduction.
    """

    def __init__(self, pk_ids: np.ndarray, counts: np.ndarray,
                 sums: np.ndarray, npart: np.ndarray, n_partitions: int,
                 n_configs: int, mesh=None):
        jax, jnp = _jnp()
        self.n_partitions = n_partitions
        self.n_configs = n_configs
        self.n_groups = len(pk_ids)
        self._mesh = mesh
        if mesh is not None:
            from jax.sharding import NamedSharding
            from pipelinedp_tpu.parallel import sharded
            self._padded_p = sharded.padded_num_partitions(
                mesh, max(n_partitions, 1))
            n_dev = mesh.devices.size
            g = len(pk_ids)
            g_pad = ((g + n_dev - 1) // n_dev) * n_dev if g else n_dev
            # Padding groups point at the out-of-range partition id
            # padded_p, which segment_sum drops.
            def pad(a, dtype, fill):
                out = np.full(g_pad, fill, dtype=dtype)
                out[:g] = np.asarray(a, dtype=dtype)
                return out
            row_sharding = NamedSharding(mesh, sharded._spec(mesh))
            self._counts = jax.device_put(pad(counts, np.float32, 0.0),
                                          row_sharding)
            self._sums = jax.device_put(pad(sums, np.float32, 0.0),
                                        row_sharding)
            self._pk_ids = jax.device_put(
                pad(pk_ids, np.int32, self._padded_p), row_sharding)
            self._npart = jax.device_put(pad(npart, np.float32, 1.0),
                                         row_sharding)
        else:
            self._padded_p = n_partitions
            self._counts = jnp.asarray(np.asarray(counts, dtype=np.float32))
            self._sums = jnp.asarray(np.asarray(sums, dtype=np.float32))
            self._pk_ids = jnp.asarray(np.asarray(pk_ids, dtype=np.int32))
            self._npart = jnp.asarray(np.asarray(npart, dtype=np.float32))
        self.metrics: List[_MetricGrids] = []
        self._moments = None  # [3, C, P] device array when computed
        # Exact (float64, host) per-partition raw values of the first
        # metric, for report-size bucketing; set by the builder
        # (per_partition._build_device_sweep). The device raw is float32
        # and could straddle a 1-2-5 bucket boundary.
        self.exact_sizes: Optional[np.ndarray] = None
        self._lazy_views: List["LazyMetricErrorArrays"] = []

    def _config_chunk(self, per_config_elements: int) -> int:
        return max(
            1,
            min(self.n_configs,
                _CHUNK_ELEMENT_BUDGET // max(per_config_elements, 1)))

    def add_metric(self, metric_kind: str, lo: np.ndarray, hi: np.ndarray,
                   l0: np.ndarray, std_noise: np.ndarray) -> int:
        """Computes one metric's error grids on device; returns its index.

        metric_kind: "sum" | "count" | "privacy_id_count".
        """
        _, jnp = _jnp()
        if self._mesh is not None:
            kernel = _mesh_metric_kernel(self._mesh, self._padded_p,
                                         metric_kind)
            n_dev = self._mesh.devices.size
            step = self._config_chunk(max(self.n_groups // n_dev, 1))
            grid_axis = 2  # mesh layout is [P, 4, C]
        else:
            # The single-metric case IS the 1-tuple case of the fused
            # kernel — one error-model body to maintain per backend.
            kernel = _kernels()[3]
            step = self._config_chunk(self.n_groups)
            grid_axis = 1
        raw = None
        parts = []
        for s in range(0, self.n_configs, step):
            e = min(s + step, self.n_configs)
            clo = jnp.asarray(np.asarray(lo[s:e], dtype=np.float32))
            chi = jnp.asarray(np.asarray(hi[s:e], dtype=np.float32))
            cl0 = jnp.asarray(np.asarray(l0[s:e], dtype=np.float32))
            if self._mesh is not None:
                r, grids = kernel(self._counts, self._sums, self._pk_ids,
                                  self._npart, clo, chi, cl0)
            else:
                ((r, grids),) = kernel(self._counts, self._sums,
                                       self._pk_ids, self._npart,
                                       clo[None, :], chi[None, :], cl0,
                                       n_partitions=self.n_partitions,
                                       metric_kinds=(metric_kind,))
            if raw is None:
                raw = r
            parts.append(grids)
        grids = parts[0] if len(parts) == 1 else jnp.concatenate(
            parts, axis=grid_axis)
        self.metrics.append(
            _MetricGrids(raw=raw,
                         grids=grids,
                         std_noise=np.asarray(std_noise, dtype=np.float64),
                         metric_kind=metric_kind))
        return len(self.metrics) - 1

    def add_metrics(self, metric_kinds, los, his, l0,
                    std_noises) -> List[int]:
        """All metrics' error grids in one device dispatch (single-device
        path; the mesh path runs per-metric kernels). Equivalent to
        calling add_metric per metric — pinned by tests — but pays one
        launch round trip instead of len(metrics), and computes the shared
        keep-probability ratio once."""
        if self._mesh is not None or not metric_kinds:
            return [
                self.add_metric(kind, lo, hi, l0, std)
                for kind, lo, hi, std in zip(metric_kinds, los, his,
                                             std_noises)
            ]
        _, jnp = _jnp()
        kernel = _kernels()[3]
        # Chunk by the FUSED footprint — the single-metric element count
        # times the metric count. XLA's buffer assignment usually reuses
        # the big [4, C, G] intermediates between the kernel's
        # data-independent metric blocks, but the admitted worst case (no
        # reuse) is len(metric_kinds) x the single-metric peak, which
        # OOMed smaller-HBM accelerators when chunking ignored the metric
        # count. Dividing the budget by len(metric_kinds) keeps the
        # worst case inside the same envelope as add_metric.
        step = self._config_chunk(self.n_groups * len(metric_kinds))
        parts = [[] for _ in metric_kinds]
        raws = [None] * len(metric_kinds)
        lo_arr = np.asarray(los, dtype=np.float32)
        hi_arr = np.asarray(his, dtype=np.float32)
        for s in range(0, self.n_configs, step):
            e = min(s + step, self.n_configs)
            outs = kernel(self._counts, self._sums, self._pk_ids,
                          self._npart, jnp.asarray(lo_arr[:, s:e]),
                          jnp.asarray(hi_arr[:, s:e]),
                          jnp.asarray(np.asarray(l0[s:e],
                                                 dtype=np.float32)),
                          n_partitions=self.n_partitions,
                          metric_kinds=tuple(metric_kinds))
            for m, (r, grids) in enumerate(outs):
                if raws[m] is None:
                    raws[m] = r
                parts[m].append(grids)
        indices = []
        for m, kind in enumerate(metric_kinds):
            grids = (parts[m][0] if len(parts[m]) == 1 else
                     jnp.concatenate(parts[m], axis=1))
            self.metrics.append(
                _MetricGrids(raw=raws[m],
                             grids=grids,
                             std_noise=np.asarray(std_noises[m],
                                                  dtype=np.float64),
                             metric_kind=kind))
            indices.append(len(self.metrics) - 1)
        return indices

    def materialize_metric(self, index: int) -> Dict[str, np.ndarray]:
        """Pulls one metric's grids to host numpy (float64), in the
        MetricErrorArrays field layout."""
        m = self.metrics[index]
        if m.grids is None:
            raise RuntimeError(
                "DeviceSweep.release(materialize=False) already dropped the "
                "device grids; materialize before releasing to keep "
                "per-partition access working.")
        grids = np.asarray(m.grids, dtype=np.float64)
        if self._mesh is not None:
            # Mesh layout is [P_pad, 4, C]: transpose and trim the padding.
            grids = np.transpose(grids, (1, 2, 0))[:, :, :self.n_partitions]
        raw = self.pull_raw(index)
        return {
            "raw": np.broadcast_to(raw,
                                   (self.n_configs,
                                    self.n_partitions)).copy(),
            "clip_min_err": grids[0],
            "clip_max_err": grids[1],
            "exp_l0_err": grids[2],
            "var_l0_err": grids[3],
        }

    def pull_raw(self, index: int) -> np.ndarray:
        """[P] raw per-partition values of one metric (host float64)."""
        raw = np.asarray(self.metrics[index].raw, dtype=np.float64)
        return raw[:self.n_partitions]

    def compute_moments(self, l0: np.ndarray) -> None:
        """Computes the [3, C, P] keep-probability moment grids on device
        (configurations sharing an L0 bound share the kernel work)."""
        _, jnp = _jnp()
        l0 = np.asarray(l0, dtype=np.float32)
        uniq, inverse = np.unique(l0, return_inverse=True)
        if self._mesh is not None:
            kernel = _mesh_moment_kernel(self._mesh, self._padded_p)
            n_dev = self._mesh.devices.size
            step = self._config_chunk(max(self.n_groups // n_dev, 1))
            cfg_axis = 2  # [P, 3, C]
        else:
            kernel = _kernels()[0]
            step = self._config_chunk(self.n_groups)
            cfg_axis = 1
        parts = []
        for s in range(0, len(uniq), step):
            e = min(s + step, len(uniq))
            if self._mesh is not None:
                parts.append(
                    kernel(self._pk_ids, self._npart, jnp.asarray(uniq[s:e])))
            else:
                parts.append(
                    kernel(self._pk_ids, self._npart, jnp.asarray(uniq[s:e]),
                           n_partitions=self.n_partitions))
        grids = parts[0] if len(parts) == 1 else jnp.concatenate(
            parts, axis=cfg_axis)
        self._moments = jnp.take(grids, jnp.asarray(inverse), axis=cfg_axis)

    def pull_moments(self) -> Optional[np.ndarray]:
        if self._moments is None:
            return None
        moments = np.asarray(self._moments, dtype=np.float64)
        if self._mesh is not None:
            moments = np.transpose(moments,
                                   (1, 2, 0))[:, :, :self.n_partitions]
        return moments

    def drop_inputs(self) -> None:
        """Frees the uploaded input columns and the moments grid — called
        by the builder once all kernels have run; only the per-metric
        grids (lazy host materialization, report reduction) stay
        resident."""
        self._counts = self._sums = self._pk_ids = self._npart = None
        self._moments = None

    def release(self, materialize: bool = True) -> None:
        """Frees the device-resident grids (HBM held otherwise lives as
        long as the analysis result).

        materialize=True first pulls every metric's grids into its lazy
        host views so per-partition consumers keep working; False drops
        the device data outright (subsequent lazy access raises).
        """
        if materialize:
            for view in self._lazy_views:
                view.raw  # touch: materializes all grid fields
        for m in self.metrics:
            m.raw = None
            m.grids = None
        self.drop_inputs()

    def report_sums(
            self, bucket_ids: np.ndarray, n_buckets: int,
            keep_prob: Optional[np.ndarray]
    ) -> Tuple[List[np.ndarray], Optional[np.ndarray]]:
        """Fused cross-partition reduction.

        Returns (per-metric [B, N_REPORT_FIELDS, C] sums,
        [B, 2, C] keep sums or None for public partitions). Only these
        small arrays leave the device.
        """
        jax, jnp = _jnp()
        if self._mesh is not None:
            return self._report_sums_mesh(bucket_ids, n_buckets, keep_prob)
        report_kernel, keep_kernel = _kernels()[1:3]
        dbuckets = jnp.asarray(np.asarray(bucket_ids, dtype=np.int32))
        if keep_prob is None:
            dkeep = jnp.ones((self.n_configs, self.n_partitions),
                             dtype=jnp.float32)
        else:
            dkeep = jnp.asarray(np.asarray(keep_prob, dtype=np.float32))
        step = self._config_chunk(self.n_partitions * N_REPORT_FIELDS)
        metric_sums = []
        for m in self.metrics:
            parts = []
            for s in range(0, self.n_configs, step):
                e = min(s + step, self.n_configs)
                parts.append(
                    report_kernel(m.raw, m.grids[:, s:e],
                                  jnp.asarray(
                                      m.std_noise[s:e].astype(np.float32)),
                                  dkeep[s:e], dbuckets,
                                  n_buckets=n_buckets))
            sums = (parts[0] if len(parts) == 1 else jnp.concatenate(
                parts, axis=2))
            metric_sums.append(np.asarray(sums, dtype=np.float64))
        ksums = None
        if keep_prob is not None:
            ksums = np.asarray(keep_kernel(dkeep, dbuckets,
                                           n_buckets=n_buckets),
                               dtype=np.float64)
        return metric_sums, ksums

    def _report_sums_mesh(self, bucket_ids, n_buckets, keep_prob):
        """Mesh twin of report_sums: per-shard bucket reductions + psum.

        Padding partitions carry the extra bucket id n_buckets and zero
        keep probability; the extra bucket row is trimmed before return.
        """
        jax, jnp = _jnp()
        from jax.sharding import NamedSharding
        from pipelinedp_tpu.parallel import sharded

        pad_p = self._padded_p
        part_sharding = NamedSharding(self._mesh,
                                      sharded._part_spec(self._mesh))
        buckets_padded = np.full(pad_p, n_buckets, dtype=np.int32)
        buckets_padded[:self.n_partitions] = np.asarray(bucket_ids,
                                                        dtype=np.int32)
        dbuckets = jax.device_put(buckets_padded, part_sharding)
        keep_pc = np.zeros((pad_p, self.n_configs), dtype=np.float32)
        if keep_prob is None:
            keep_pc[:self.n_partitions, :] = 1.0
        else:
            keep_pc[:self.n_partitions, :] = np.asarray(
                keep_prob, dtype=np.float32).T
        with_keep = keep_prob is not None
        kernel = _mesh_report_kernel(self._mesh, n_buckets + 1, with_keep)
        metric_sums = []
        ksums = None
        dkeep = jax.device_put(keep_pc, part_sharding)
        for i, m in enumerate(self.metrics):
            out = kernel(m.raw, m.grids,
                         jnp.asarray(m.std_noise.astype(np.float32)), dkeep,
                         dbuckets)
            if with_keep:
                sums, ks = out
                if i == 0:
                    ksums = np.asarray(ks, dtype=np.float64)[:n_buckets]
            else:
                sums = out
            metric_sums.append(
                np.asarray(sums, dtype=np.float64)[:n_buckets])
        return metric_sums, ksums


class LazyMetricErrorArrays:
    """MetricErrorArrays twin whose [C, P] grids materialize from the
    device on first attribute access (per_partition.MetricErrorArrays is
    the eager host equivalent)."""

    _GRID_FIELDS = ("raw", "clip_min_err", "clip_max_err", "exp_l0_err",
                    "var_l0_err")

    def __init__(self, metric, std_noise, noise_kind, sweep: DeviceSweep,
                 index: int):
        self.metric = metric
        self.std_noise = std_noise
        self.noise_kind = noise_kind
        self._sweep = sweep
        self._index = index
        sweep._lazy_views.append(self)

    def __getattr__(self, name):
        if name in LazyMetricErrorArrays._GRID_FIELDS:
            self.__dict__.update(
                self._sweep.materialize_metric(self._index))
            return self.__dict__[name]
        raise AttributeError(name)
