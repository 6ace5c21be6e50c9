"""Back-to-back batches of one query under a sweep of contribution bounds,
served from one resident dataset.

Set-up ingests a ``serving.DatasetSession`` from the host columns (its
wire device-resident, so no query ships it again), registers one tenant
whose ledger, release journal and audit trail every release goes
through, as a served deployment's do, and runs one warm-up batch. The
window then calls ``query_batch`` in a closed loop: each batch holds one
config per l0 of the traffic's ladder, identical otherwise, each with a
fresh kernel seed (``Seeds.engine(lanes * i + b)``), so that no config
is answered from the bound cache and every batch replays the wire in one
fused launch group.

``reference.verdict`` needs one release per item, so each config of a
batch is one item (``work`` = the dataset's rows) with its batch's start
and end; the batch's stage times ride on its first item alone, so that
readers total them per batch. A batch whose plan is not one fused group
of every lane with no cache skip, whose call raised, or during which a
resilience counter moved, fails all of its items.
"""

from __future__ import annotations

import sys
import time
import traceback

import numpy as np

from benchmark import common, reference

TENANT = "sweep"
# Warm-up kernel seeds come from their own stream tag (the window's
# engine seeds use tag 1).
WARMUP_TAG = 2


class Driver:

    def __init__(self, cfg: dict, traffic: dict, columns,
                 seeds: common.Seeds):
        import pipelinedp_tpu as pdp
        pid, pk, value = columns
        self._data = pdp.ColumnarData(pid=pid, pk=pk, value=value)
        self._query = cfg["aggregate"]
        self._ladder = [int(l0) for l0 in traffic["l0"]]
        self._budget_batches = int(traffic["budget_batches"])
        self._seeds = seeds
        self.n_rows = len(pid)
        self._session = None
        self._keys = None
        self._batches = 0  # batches run so far: seeds never repeat

    def query(self, l0: int) -> dict:
        """The configuration's query with this l0, as the reference reads
        it."""
        return dict(self._query, max_partitions_contributed=l0)

    def _configs(self, seeds):
        from pipelinedp_tpu import serving
        q = self._query
        params = common.aggregate_params(self.query(self._ladder[0]))
        return [serving.QueryConfig(
            metrics=params.metrics, epsilon=q["epsilon"], delta=q["delta"],
            noise_kind=params.noise_kind, max_partitions_contributed=l0,
            max_contributions_per_partition=q[
                "max_contributions_per_partition"],
            min_value=q["min_value"], max_value=q["max_value"],
            seed=seed, tenant=TENANT)
            for l0, seed in zip(self._ladder, seeds)]

    def setup(self) -> None:
        """Ingest, the tenant, and one warm-up batch, which loads or
        compiles every program the window runs."""
        from pipelinedp_tpu import serving
        self._session = serving.DatasetSession(self._data)
        if not self._session._wire.device_resident:
            raise RuntimeError("the session's wire is not device-resident")
        self._keys = np.asarray(self._session.pk_vocab.keys)
        lanes = len(self._ladder)
        releases = lanes * self._budget_batches
        self._session.register_tenant(
            TENANT, total_epsilon=self._query["epsilon"] * releases,
            total_delta=self._query["delta"] * releases)
        warm = [int(np.random.SeedSequence(
            [self._seeds.seed, WARMUP_TAG, b]).generate_state(1)[0])
            & 0x7FFFFFFF for b in range(lanes)]
        self._session.query_batch(self._configs(warm))

    def _planner(self) -> dict:
        return self._session.stats()["planner"]

    def _batch(self, i: int, window: common.Window, t0: float) -> None:
        import jax
        from pipelinedp_tpu import profiler

        lanes = len(self._ladder)
        items = [common.Item(work=self.n_rows) for _ in range(lanes)]
        seeds = [self._seeds.engine(lanes * i + b) for b in range(lanes)]
        before = common.resilience_counters()
        plan0 = self._planner()
        start = time.perf_counter() - t0
        outs, error = None, None
        try:
            with jax.profiler.TraceAnnotation("bench/query_batch"), \
                    profiler.collect_stage_times() as stages:
                outs = self._session.query_batch(self._configs(seeds))
            items[0].stages = dict(stages)
        except Exception as exc:  # a failed batch is counted, not fatal
            error = f"{type(exc).__name__}: {exc}"
            traceback.print_exc(file=sys.stderr)
        end = time.perf_counter() - t0
        if error is None:
            plan = {k: v - plan0[k] for k, v in self._planner().items()
                    if k in ("batches", "lanes", "fused_groups",
                             "cache_skips", "dedupes")}
            want = {"batches": 1, "lanes": lanes, "fused_groups": 1,
                    "cache_skips": 0, "dedupes": 0}
            if plan != want:
                error = f"planner ran {plan}, expected {want}"
        moved = {k: v - before[k]
                 for k, v in common.resilience_counters().items()
                 if v != before[k]}
        if error is None and moved:
            error = f"resilience counters moved: {moved}"
        for b, item in enumerate(items):
            item.start, item.end = start, end
            item.ok, item.error = error is None, error
            window.items.append(item)
            if item.ok:
                window.releases.append((
                    self.query(self._ladder[b]),
                    _release(outs[b], self._keys, self._query["metrics"])))

    def window(self, seconds: float) -> common.Window:
        out = common.Window()
        t0 = time.perf_counter()
        while True:
            self._batch(self._batches, out, t0)
            self._batches += 1
            if time.perf_counter() - t0 >= seconds:
                break
        out.seconds = time.perf_counter() - t0
        return out

    def close(self) -> None:
        if self._session is not None:
            self._session.close()
        self._session = None
        self._data = None


def _release(cols: dict, keys: np.ndarray, metrics) -> reference.Release:
    """The kept partition keys and, per metric, their released values, of
    one config's released columns."""
    keep = np.asarray(cols["keep_mask"])
    ids = np.asarray(cols["partition_id"])[keep]
    return reference.Release(
        keys=keys[ids],
        values={m: np.asarray(cols[common.COLUMN[m]])[keep]
                for m in metrics})
