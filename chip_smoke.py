"""Bring-up check: the main path on the chip, through the public API, at
the headline deployment shape.

    python chip_smoke.py             # one TPU chip: phases A, A', B
    python chip_smoke.py --chips 4   # one host, four chips: the mesh phase

Data: a movie-ratings-shaped workload — 100M rows,
1M partitions, 10M privacy units, partition popularity pk = P * u**4,
integer star ratings 1..5 — generated from ``--seed``. The mesh phase
runs the same shape at 10M rows (MESH_SHAPE says why).

Phases (one chip):
  A   cold ``JaxDPEngine.aggregate``: COUNT+SUM, private partition
      selection, eps=1, delta=1e-6, l0=8, linf=4; called twice (the first
      call compiles).
  A'  public partitions, caps computed on the host so that nothing is
      bounded, Gaussian noise whose stddev the engine reports; released
      COUNT and SUM are compared with exact ``np.bincount`` results under
      a bound a correct run breaks with probability < 1e-6.
  B   ``serving.DatasetSession`` on the same columns: warm queries with
      different caps, one ``query_batch`` of width 8, and the phase-A
      config bit-identical to a cold engine run with the same seed.

``--chips 4`` runs only the mesh phase: A' on a 2x2 mesh and on one chip,
a mesh session query bit-identical to the mesh cold run, and a check that
the work really spread over all four devices.

Every phase ends by asserting that no resilience path fired (retries,
degradations, resumes, native fallbacks, watchdog timeouts, serving
device fallbacks): on a bring-up a quiet degradation is a failure.

Earlier output lines are one JSON object per phase (diagnostics, not
benchmark numbers). The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``
and is printed only when every phase passed. Without a TPU the script
exits non-zero before any data is made.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

EPS, DELTA = 1.0, 1e-6
L0_CAP, LINF_CAP = 8, 4
MIN_VALUE, MAX_VALUE = 0.0, 5.0
# Probability that a correct run fails the reference check, over all
# released values of one aggregate.
REFERENCE_FAIL_PROB = 1e-6
BATCH_WIDTH = 8


@dataclasses.dataclass(frozen=True)
class Shape:
    n_rows: int = 100_000_000
    n_partitions: int = 1_000_000
    n_users: int = 10_000_000


# The mesh phase keeps the partition count, the popularity law and the
# rows per privacy unit, and cuts rows to a tenth: its chunk program
# compiles in ~85 s at 1.25M rows per device (10M rows) but ~630 s at
# 3.1M (25M rows), and the headline would put 12.5M on each device
# (rehearsal for a described v5e 2x2; CHANGES.md, PR 21) — more compile
# than a four-chip call can afford on top of the run.
MESH_SHAPE = Shape(n_rows=10_000_000, n_users=1_000_000)


class SmokeFailure(AssertionError):
    """A phase produced a wrong or degraded result."""


def _check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu(n_chips: int):
    """The devices, or exit non-zero: this script never runs on the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: no TPU (JAX reports {devices[0].platform!r}); "
            "refusing to run\n")
        sys.exit(2)
    if len(devices) < n_chips:
        sys.stderr.write(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"TPU devices, JAX reports {len(devices)}\n")
        sys.exit(2)
    return devices


def make_columns(shape: Shape, seed: int):
    """(pid int32, pk int32, value float32) of the headline workload."""
    rng = np.random.default_rng(seed)
    pk = (shape.n_partitions * rng.random(shape.n_rows)**4).astype(np.int32)
    np.minimum(pk, shape.n_partitions - 1, out=pk)
    pid = rng.integers(0, shape.n_users, shape.n_rows, dtype=np.int32)
    value = rng.integers(1, 6, shape.n_rows, dtype=np.int8).astype(
        np.float32)
    return pid, pk, value


def exact_caps(pid: np.ndarray, pk: np.ndarray, n_partitions: int):
    """(l0, linf) that bound nothing: the most distinct partitions of any
    privacy unit, and the most rows of any (privacy unit, partition)."""
    pair = pid.astype(np.int64) * n_partitions + pk
    uniq, runs = np.unique(pair, return_counts=True)
    per_pid = np.bincount(uniq // n_partitions)
    return int(per_pid.max()), int(runs.max())


def _counters() -> dict:
    from pipelinedp_tpu import profiler, runtime
    from pipelinedp_tpu.serving import session as session_lib

    c = runtime.resilience_counters()
    out = {k: c[k] for k in ("retries", "degradations", "resumes",
                              "native_fallbacks", "watchdog_timeouts",
                              "hangs_detected")}
    out["device_fallbacks"] = profiler.event_count(
        session_lib.EVENT_DEVICE_FALLBACKS)
    return out


def _sampler_counters() -> dict:
    from pipelinedp_tpu import profiler
    from pipelinedp_tpu.ops import columnar

    return {
        "hash_passes": profiler.event_count(columnar.sampler_event("hash")),
        "hash_demotions": profiler.event_count(
            columnar.EVENT_HASH_DEMOTIONS),
        "sorted_chunks": sum(
            profiler.event_count(columnar.sampler_event(kind))
            for kind in ("tiled", "packed", "general")),
    }


def _sampler_name(before: dict, after: dict) -> str:
    """The group stage the chunk kernels ran, read off the per-chunk
    counters the slab driver credits."""
    d = {k: after[k] - before[k] for k in before}
    if d["hash_passes"] and not d["hash_demotions"]:
        return "hash"
    if d["hash_passes"]:
        return f"hash+{d['hash_demotions']}_demoted"
    return "sorted" if d["sorted_chunks"] else "none"


def _peak_bytes(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def _native_loaded() -> dict:
    from pipelinedp_tpu.native import loader
    return {"secure_noise": loader.load() is not None,
            "row_packer": loader.load_row_packer() is not None}


class Phase:
    """Times a phase and fails it if a resilience counter moved."""

    def __init__(self, name: str, devices):
        self.name = name
        self.devices = devices
        self.info: dict = {}

    def __enter__(self):
        self._counters = _counters()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        moved = {k: v - self._counters[k] for k, v in _counters().items()
                 if v != self._counters[k]}
        _check(not moved, f"phase {self.name}: device path degraded: "
                          f"{moved}")
        d0 = self.devices[0]
        record = {"phase": self.name,
                  "device_kind": d0.device_kind,
                  "device_count": len(self.devices),
                  "wall_s": time.perf_counter() - self._t0,
                  "peak_bytes_in_use": _peak_bytes(self.devices)}
        record.update(self.info)
        print(json.dumps(record), flush=True)
        return False


def headline_params():
    import pipelinedp_tpu as pdp
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        max_partitions_contributed=L0_CAP,
        max_contributions_per_partition=LINF_CAP,
        min_value=MIN_VALUE, max_value=MAX_VALUE)


def _aggregate(data, params, *, seed, public_partitions=None, **engine_kw):
    import pipelinedp_tpu as pdp
    accountant = pdp.NaiveBudgetAccountant(EPS, DELTA)
    engine = pdp.JaxDPEngine(accountant, seed=seed, **engine_kw)
    result = engine.aggregate(data, params,
                              public_partitions=public_partitions)
    accountant.compute_budgets()
    return result


def _check_released(cols: dict, what: str) -> int:
    keep = np.asarray(cols["keep_mask"])
    n_kept = int(keep.sum())
    _check(n_kept > 0, f"{what}: no partition kept")
    for name in ("count", "sum"):
        _check(np.isfinite(np.asarray(cols[name])[keep]).all(),
               f"{what}: non-finite released {name}")
    return n_kept


def _assert_same_release(a: dict, b: dict, what: str) -> None:
    for name in ("keep_mask", "count", "sum"):
        try:
            np.testing.assert_array_equal(np.asarray(a[name]),
                                          np.asarray(b[name]))
        except AssertionError as e:
            raise SmokeFailure(f"{what}: {name} differs: {e}") from None


def phase_a(data, devices, seed: int) -> None:
    with Phase("A", devices) as ph:
        secs, kept = [], []
        before = _sampler_counters()
        for _ in range(2):
            t0 = time.perf_counter()
            cols = _aggregate(data, headline_params(), seed=seed).to_columns()
            kept.append(_check_released(cols, "phase A"))
            secs.append(time.perf_counter() - t0)
        ph.info.update(first_call_s=secs[0], second_call_s=secs[1],
                       kept_partitions=kept,
                       sampler=_sampler_name(before, _sampler_counters()),
                       native=_native_loaded())


def exact_params(caps):
    """Phase A' parameters: caps that bound nothing, Gaussian noise whose
    stddev the engine reports in the released columns."""
    import pipelinedp_tpu as pdp
    l0, linf = caps
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        noise_kind=pdp.NoiseKind.GAUSSIAN,
        max_partitions_contributed=l0,
        max_contributions_per_partition=linf,
        min_value=MIN_VALUE, max_value=MAX_VALUE,
        output_noise_stddev=True)


def check_reference(result, data, shape: Shape, what: str) -> dict:
    """Released COUNT and SUM of an unbounded public-partition aggregate
    against exact np.bincount results."""
    p = shape.n_partitions
    cols = result.to_columns()
    keys = np.asarray(result.partition_keys())
    _check(len(keys) == p, f"{what}: {len(keys)} of {p} public "
                           f"partitions released")
    pk, value = np.asarray(data.pk), np.asarray(data.value)
    ref = {"count": np.bincount(pk, minlength=p)[keys],
           "sum": np.bincount(pk, weights=np.clip(value, MIN_VALUE,
                                                  MAX_VALUE),
                              minlength=p)[keys]}
    # Gaussian tail, union over every released value of both metrics:
    # P(|N(0, s^2)| > t s) <= 2 exp(-t^2 / 2).
    n_draws = 2 * p
    t = math.sqrt(2.0 * math.log(2.0 * n_draws / REFERENCE_FAIL_PROB))
    out = {}
    for name in ("count", "sum"):
        released = np.asarray(cols[name], dtype=np.float64)
        sigma = float(np.asarray(cols[f"{name}_noise_stddev"])[0])
        _check(np.isfinite(sigma) and sigma > 0,
               f"{what}: bad {name} stddev {sigma}")
        z = (released - ref[name]) / sigma
        worst = float(np.max(np.abs(z)))
        _check(worst <= t, f"{what}: {name} off the exact reference by "
                           f"{worst:.2f} sigma (bound {t:.2f})")
        # The noise must also look like the noise the engine reports: a
        # lost chunk or a doubled one shifts the mean of z by far more.
        mean_z, std_z = float(z.mean()), float(z.std())
        _check(abs(mean_z) <= 6.0 / math.sqrt(p),
               f"{what}: {name} mean error {mean_z:.4f} sigma")
        _check(abs(std_z - 1.0) <= 0.05,
               f"{what}: {name} error spread {std_z:.4f} sigma")
        out[name] = {"sigma": sigma, "max_abs_z": worst, "bound_z": t,
                     "mean_z": mean_z, "std_z": std_z}
    return out


def reference_check(data, shape: Shape, caps, *, seed: int, what: str,
                    **engine_kw) -> dict:
    """Phase A': a cold aggregate that bounds nothing, against
    np.bincount."""
    result = _aggregate(data, exact_params(caps), seed=seed,
                        public_partitions=np.arange(shape.n_partitions),
                        **engine_kw)
    return check_reference(result, data, shape, what)


def phase_a_ref(data, shape: Shape, caps, devices, seed: int) -> None:
    with Phase("A_ref", devices) as ph:
        secs, checks = [], []
        for s in (seed, seed + 1):
            t0 = time.perf_counter()
            checks.append(reference_check(data, shape, caps, seed=s,
                                          what="phase A'"))
            secs.append(time.perf_counter() - t0)
        ph.info.update(first_call_s=secs[0], second_call_s=secs[1],
                       caps={"l0": caps[0], "linf": caps[1]},
                       reference=checks)


def _wire_mode(session) -> str:
    fmt = session._wire.fmt
    return f"pid={fmt.pid_mode},value={fmt.value.mode}"


def phase_b(data, devices, seed: int) -> None:
    import pipelinedp_tpu as pdp
    from pipelinedp_tpu import serving

    with Phase("B", devices) as ph:
        t0 = time.perf_counter()
        session = serving.DatasetSession(data, secure_host_noise=False)
        ingest_s = time.perf_counter() - t0
        stats = session.stats()
        before = _sampler_counters()
        # Warm queries with different caps. The first reuses phase A's
        # chunk program; l0 >= 16 gives the static kept-group bound of
        # phase A' (columnar.compact_group_bound), so no query compiles a
        # chunk program of its own.
        query_s = []
        for l0, linf in ((L0_CAP, LINF_CAP), (16, 2), (32, 1)):
            params = pdp.AggregateParams(
                metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
                max_partitions_contributed=l0,
                max_contributions_per_partition=linf,
                min_value=MIN_VALUE, max_value=MAX_VALUE)
            t1 = time.perf_counter()
            cols = session.query(params, epsilon=EPS, delta=DELTA,
                                 seed=seed + l0).to_columns()
            _check_released(cols, f"phase B query l0={l0} linf={linf}")
            query_s.append(time.perf_counter() - t1)

        configs = [
            serving.QueryConfig(
                metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
                epsilon=EPS * (1 + i % 2), delta=DELTA,
                max_partitions_contributed=(16, 32)[i % 2],
                max_contributions_per_partition=1 + i // 2,
                min_value=MIN_VALUE, max_value=MAX_VALUE, seed=seed + i)
            for i in range(BATCH_WIDTH)
        ]
        t1 = time.perf_counter()
        batch = session.query_batch(configs)
        batch_s = time.perf_counter() - t1
        _check(len(batch) == BATCH_WIDTH, "phase B: batch width")
        for i, cols in enumerate(batch):
            _check_released(cols, f"phase B batch config {i}")
        alone = session.query(configs[3].to_params(),
                              epsilon=configs[3].epsilon,
                              delta=configs[3].delta,
                              seed=configs[3].seed).to_columns()
        _assert_same_release(batch[3], alone, "phase B batch vs query")

        # Warm/cold parity (SERVING.md "Exactness"): the phase-A config
        # under the phase-A seed, device noise on both sides.
        t1 = time.perf_counter()
        warm = session.query(headline_params(), epsilon=EPS, delta=DELTA,
                             seed=seed).to_columns()
        parity_warm_s = time.perf_counter() - t1
        t1 = time.perf_counter()
        cold = _aggregate(data, headline_params(), seed=seed,
                          secure_host_noise=False,
                          stream_chunks=session.n_chunks).to_columns()
        parity_cold_s = time.perf_counter() - t1
        _assert_same_release(warm, cold, "phase B warm vs cold")
        ph.info.update(
            ingest_s=ingest_s, first_call_s=query_s[0],
            second_call_s=query_s[1], query_s=query_s,
            batch_width=BATCH_WIDTH, batch_s=batch_s,
            parity_warm_s=parity_warm_s, parity_cold_s=parity_cold_s,
            device_resident=stats["wire_device_bytes"] > 0,
            wire_host_bytes=stats["wire_host_bytes"],
            n_chunks=session.n_chunks, wire=_wire_mode(session),
            sampler=_sampler_name(before, _sampler_counters()))
        session.close()


def phase_mesh(data, shape: Shape, caps, devices, seed: int) -> None:
    """--chips 4: the reference-checked aggregate on a 2x2 mesh and on one
    chip, and a mesh session query bit-identical to the mesh cold run.

    Every mesh step runs the phase-A' config, so the mesh compiles one
    chunk program (it compiles several times slower than its one-chip
    twin; CHANGES.md, PR 21)."""
    from pipelinedp_tpu import serving
    from pipelinedp_tpu.parallel import sharded

    n = len(devices)
    mesh = sharded.make_mesh(n)
    public = np.arange(shape.n_partitions)
    with Phase("mesh", devices) as ph:
        t0 = time.perf_counter()
        mesh_ref = reference_check(data, shape, caps, seed=seed,
                                   what="mesh A'", mesh=mesh)
        mesh_s = time.perf_counter() - t0
        # Rows are hash-sharded by privacy id and the partition dimension
        # is reduce-scattered, so every device must have held a real
        # share of the work. Read before the one-chip run touches
        # device 0 again.
        peaks = _peak_bytes(devices)
        _check(all(isinstance(b, int) for b in peaks),
               f"mesh: memory_stats unavailable: {peaks}")
        acc_shard = 4 * shape.n_partitions // n
        _check(min(peaks) >= acc_shard and min(peaks) >= max(peaks) // 4,
               f"mesh: work did not span the devices: peaks {peaks}")

        t0 = time.perf_counter()
        session = serving.DatasetSession(data, mesh=mesh,
                                         public_partitions=public,
                                         secure_host_noise=False)
        ingest_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm = session.query(exact_params(caps), epsilon=EPS, delta=DELTA,
                             seed=seed)
        warm_ref = check_reference(warm, data, shape, "mesh session query")
        warm_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        cold = _aggregate(data, exact_params(caps), seed=seed, mesh=mesh,
                          public_partitions=public, secure_host_noise=False,
                          stream_chunks=session.n_chunks).to_columns()
        cold_s = time.perf_counter() - t0
        _assert_same_release(warm.to_columns(), cold, "mesh warm vs cold")
        n_chunks = session.n_chunks
        session.close()

        t0 = time.perf_counter()
        one_ref = reference_check(data, shape, caps, seed=seed,
                                  what="one-chip A'")
        one_s = time.perf_counter() - t0
        ph.info.update(
            mesh_shape=dict(mesh.shape), mesh_ref_s=mesh_s,
            one_chip_ref_s=one_s, mesh_reference=mesh_ref,
            one_chip_reference=one_ref, session_reference=warm_ref,
            peaks_after_mesh=peaks, caps={"l0": caps[0], "linf": caps[1]},
            session_ingest_s=ingest_s, warm_query_s=warm_s,
            cold_run_s=cold_s, n_chunks=n_chunks)


def run(shape: Shape, seed: int, devices, chips: int) -> None:
    """Every phase for ``chips``; raises SmokeFailure on a wrong or
    degraded result."""
    import pipelinedp_tpu as pdp

    t0 = time.perf_counter()
    pid, pk, value = make_columns(shape, seed)
    caps = exact_caps(pid, pk, shape.n_partitions)
    print(json.dumps({"phase": "data", "rows": shape.n_rows,
                      "partitions": shape.n_partitions,
                      "users": shape.n_users, "exact_caps": caps,
                      "wall_s": time.perf_counter() - t0}), flush=True)
    data = pdp.ColumnarData(pid=pid, pk=pk, value=value)
    if chips == 1:
        phase_a(data, devices[:1], seed)
        phase_a_ref(data, shape, caps, devices[:1], seed)
        phase_b(data, devices[:1], seed)
    else:
        phase_mesh(data, shape, caps, devices[:chips], seed)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    devices = require_tpu(args.chips)
    # Before the first encode: a failed build of the native encoder or
    # noise sampler raises instead of quietly running the numpy twin.
    os.environ["PIPELINEDP_TPU_REQUIRE_NATIVE"] = "1"
    from pipelinedp_tpu import compile_cache
    cache_dir = compile_cache.configure(ROOT)
    print(json.dumps({"phase": "setup", "compile_cache": cache_dir,
                      "devices": [str(d) for d in devices]}), flush=True)

    run(Shape() if args.chips == 1 else MESH_SHAPE, args.seed, devices,
        args.chips)
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
