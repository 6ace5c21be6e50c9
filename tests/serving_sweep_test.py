"""The ``movielens25m.sweep`` cell at test size: a MovieLens-shaped draw
from the cell's own generator, served from one resident session, one
query asked as a batch of eight configs that differ only in l0.

Contracts:
  * the generator's profiles keep the published totals at full size,
    without drawing the rows; a draw has one row per (user, movie) pair,
    half-star values, every movie, and rows in user order;
  * half stars ship as bit-planes at scale 0.5, exactly;
  * each lane of ``query_batch`` (the batched sampler, wide payload) is
    bit-identical to the same config's ``session.query`` and to a cold
    ``JaxDPEngine.aggregate`` (the packed sort with the narrow payload:
    the heaviest user's run is too long for hash bins);
  * the cell's run (generator, driver, reference, metrics) is correct,
    and comes out not correct with the control (no contribution
    bounding) or a planted fault in the program's place: lane 0's
    accumulators returned for lane 7, a chunk's rows lost, half the rows
    left out, an answer altered.

The test size keeps the cell's query but for epsilon: at 520 users the
l0=128 lane's selection threshold (~l0 / epsilon units per partition)
would release nothing at epsilon 1, and a fault in it would not show.
"""

import copy
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import serving
from pipelinedp_tpu.ops import columnar, wirecodec

from benchmark import run
from benchmark.generators import movielens_25m

CELL = "movielens25m.sweep"
BENCH = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
SMALL_DATA = {"n_rows": 80_000, "n_partitions": 600, "n_users": 520,
              "movie_id_max": 1_800,
              "user_rows": {"median": 71, "min": 20, "max": 590},
              "movie_rows": {"median": 20, "min": 1, "max": 500}}
SMALL_EPSILON = 64.0
# Set for this size: sound rehearsals on twenty seeds read up to z_max
# 6.6, z_sd_gap 0.061, z_sd_gap_top 0.055, kept_gap 2.2 (a few hundred
# released partitions spread wider than the cell's); the faults read at
# least z_max 11.5 (an answer altered), z_sd_gap 0.67 (a chunk lost, half
# the rows left out) or kept_gap 56 (the lane swap).
TEST_LIMITS = {"z_max": 9.0, "z_sd_gap": 0.2, "z_sd_gap_top": 0.2,
               "kept_gap": 8.0}
SEED = 2**33 + 5


def small_cell() -> run.Cell:
    cell = run.resolve(BENCH, CELL, "TPU v5 lite")
    cfg = copy.deepcopy(cell.config)
    cfg["data"].update(SMALL_DATA)
    cfg["aggregate"]["epsilon"] = SMALL_EPSILON
    return run.Cell(workload=cell.workload, config=cfg, traffic=cell.traffic,
                    limits=TEST_LIMITS, peaks=cell.peaks)


@pytest.fixture(scope="module")
def cell():
    return small_cell()


@pytest.fixture(scope="module")
def columns(cell):
    return movielens_25m.make_columns(cell.config, SEED)


@pytest.fixture(scope="module")
def session(columns):
    pid, pk, value = columns
    s = serving.DatasetSession(pdp.ColumnarData(pid=pid, pk=pk,
                                                value=value))
    yield s
    s.close()


def lane_configs(cell):
    q = cell.config["aggregate"]
    return [serving.QueryConfig(
        metrics=[getattr(pdp.Metrics, m) for m in q["metrics"]],
        epsilon=q["epsilon"], delta=q["delta"],
        noise_kind=getattr(pdp.NoiseKind, q["noise_kind"]),
        max_partitions_contributed=l0,
        max_contributions_per_partition=q["max_contributions_per_partition"],
        min_value=q["min_value"], max_value=q["max_value"], seed=100 + b)
        for b, l0 in enumerate(cell.traffic["l0"])]


@pytest.fixture(scope="module")
def batch(cell, session):
    configs = lane_configs(cell)
    return configs, session.query_batch(configs, secure_host_noise=False)


def assert_columns_identical(a: dict, b: dict):
    assert list(a) == list(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


class TestGenerator:

    def test_profiles_sum_to_the_published_totals(self):
        cfg = run.load_json(os.path.join(
            run.ROOT, "benchmark", "configs", "movielens25m.json"))
        users, movies = movielens_25m.profiles(cfg)
        assert len(users) == 162_541 and len(movies) == 59_047
        assert int(users.sum()) == int(movies.sum()) == 25_000_095
        assert (int(users.min()), int(users.max())) == (20, 32_202)
        assert (int(movies.min()), int(movies.max())) == (1, 81_491)
        assert sum(cfg["data"]["rating_marginals"]) == 25_000_095

    def test_draw_keeps_pairs_values_movies_and_order(self, cell, columns):
        pid, pk, value = columns
        d = cell.config["data"]
        assert len(pid) == d["n_rows"]
        assert len(np.unique(pid)) == d["n_users"]
        assert len(np.unique(pk)) == d["n_partitions"]
        key = pid.astype(np.int64) << 20 | pk
        assert (np.diff(key) > 0).all()  # user, then movie; no pair twice
        assert set(np.unique(value).tolist()) == {
            0.5 * i for i in range(1, 11)}
        assert np.bincount(pid).max() == d["user_rows"]["max"]

    def test_same_seed_same_rows(self, cell, columns):
        again = movielens_25m.make_columns(cell.config, SEED, threads=3)
        for a, b in zip(columns, again):
            np.testing.assert_array_equal(a, b)


def test_half_stars_ship_as_planes_at_half_scale(session, columns):
    plan = session._wire.fmt.value
    assert (plan.mode, plan.lo, plan.scale, plan.bits) == (
        wirecodec.VALUE_PLANES, 0.5, 0.5, 4)
    full, idx = wirecodec.plan_and_index(columns[2])
    assert full == plan
    np.testing.assert_array_equal(
        np.float32(0.5) + idx.astype(np.float32) * np.float32(0.5),
        columns[2])


def test_sequential_lanes_tile_and_the_batch_does_not(session, batch):
    # The heaviest user's run is too long for hash bins, so a config
    # asked alone takes the packed sort with the narrow plane-index
    # payload and int32 accumulation (auto never tiles); the batch
    # forces the wide-payload oracle statics, and the lane comparisons
    # below span the two.
    wire = session._wire
    assert wire.max_run == SMALL_DATA["user_rows"]["max"]
    assert wire.max_run > wirecodec.HASH_MAX_BIN_ROWS
    configs, _ = batch
    for cfg in configs:
        p = session._prepare_query(0, cfg, False)
        desc = session._resolved_sampler(None, session._batch_kw(p))
        assert desc.startswith("packed:"), desc
        assert "tile_rows=0," in desc, desc
        assert desc.endswith("sort_value_narrow=True)"), desc


@pytest.mark.parametrize("lane", range(8))
def test_lane_identical_to_query_and_cold_aggregate(cell, session,
                                                     columns, batch, lane):
    configs, outs = batch
    cfg = configs[lane]
    warm = session.query(cfg.to_params(), epsilon=cfg.epsilon,
                         delta=cfg.delta, seed=cfg.seed,
                         secure_host_noise=False).to_columns()
    assert_columns_identical(outs[lane], warm)
    pid, pk, value = columns
    accountant = pdp.NaiveBudgetAccountant(cfg.epsilon, cfg.delta)
    engine = pdp.JaxDPEngine(accountant, seed=cfg.seed,
                             secure_host_noise=False,
                             stream_chunks=session.n_chunks)
    result = engine.aggregate(pdp.ColumnarData(pid=pid, pk=pk, value=value),
                              cfg.to_params())
    accountant.compute_budgets()
    assert_columns_identical(outs[lane], result.to_columns())


def rehearse(seed: int = SEED) -> dict:
    defs = [m for m in BENCH["end_to_end"] + BENCH["per_layer"]
            if run.applies(m, CELL) and m["source"] != "device_trace"]
    return run.run_cell(small_cell(), defs, seed=seed, seconds=0.5,
                        trace=False, devices=jax.devices()[:1],
                        t_start=time.perf_counter())


def test_cell_runs_and_is_correct():
    result = rehearse()
    assert result["correct"], result["checks"]
    assert result["attempted"] % 8 == 0 and result["failed"] == 0
    wanted = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if run.applies(m, CELL) and m["source"] != "device_trace"}
    assert set(result["metrics"]) == wanted
    assert list(result)[-1] == "checks"


def _unbounded(self, group, lanes):
    """The control: every lane's accumulators without contribution
    bounding, straight from the source columns."""
    src = self._source
    p = self.num_partitions
    slot = np.zeros(int(src.pk.max()) + 1, dtype=np.int64)
    slot[np.asarray(self.pk_vocab.keys)] = np.arange(p)
    ids = slot[src.pk]
    pair = np.unique(src.pid.astype(np.int64) * p + ids)
    out = []
    for lane in lanes:
        v = np.clip(src.value, lane.row_lo, lane.row_hi)
        zero = np.zeros(p)
        out.append((np.bincount(pair % p, minlength=p),
                    np.bincount(ids, minlength=p),
                    np.bincount(ids, weights=v, minlength=p), zero, zero))
    return columnar.PartitionAccumulators(*(
        jnp.asarray(np.stack(f), dtype=jnp.float32) for f in zip(*out)))


def _lost_chunk(accs, rng):
    """A chunk step that returned its state unchanged: one eighth of the
    rows never reached the accumulators."""
    return accs._replace(**{
        f: np.floor(np.asarray(getattr(accs, f)) * 7 / 8).astype(
            np.asarray(getattr(accs, f)).dtype)
        for f in ("pid_count", "count", "sum")})


def _half_batch(accs, rng):
    """Half of the rows left out, the totals scaled up from the rest."""
    count = np.asarray(accs.count)
    kept = rng.binomial(count.astype(np.int64), 0.5)
    share = np.where(count > 0, kept / np.maximum(count, 1), 0.0)
    pids = np.asarray(accs.pid_count).astype(np.int64)
    return accs._replace(
        count=(2 * kept).astype(count.dtype),
        sum=(2 * share * np.asarray(accs.sum)).astype(
            np.asarray(accs.sum).dtype),
        pid_count=(2 * rng.binomial(pids, 0.5)).astype(
            np.asarray(accs.pid_count).dtype))


FAULTS = {"state_unchanged": _lost_chunk, "half_batch": _half_batch}


@pytest.mark.parametrize("fault", ["control", "lane_swap", "answer_altered"]
                         + sorted(FAULTS))
def test_control_and_faults_are_not_correct(fault, monkeypatch):
    from pipelinedp_tpu.ops import finalize
    rng = np.random.default_rng(7)
    if fault == "control":
        monkeypatch.setattr(serving.DatasetSession, "_replay_group_batched",
                            _unbounded)
    elif fault == "lane_swap":
        lane_accs = serving.DatasetSession._lane_accs
        monkeypatch.setattr(
            serving.DatasetSession, "_lane_accs",
            lambda self, accs_b: lane_accs(self, accs_b)[:7]
            + lane_accs(self, accs_b)[:1])
    else:
        original = finalize.host_epilogue

        def planted(plan, scalars, accs, vector_sums):
            if fault in FAULTS:
                accs = FAULTS[fault](accs, rng)
            cols, keep = original(plan, scalars, accs, vector_sums)
            if fault == "answer_altered":
                # The most popular released partition's count, halved.
                count = np.array(cols["count"], dtype=np.float64)
                top = int(np.argmax(np.where(keep, count, -np.inf)))
                count[top] *= 0.5
                cols = dict(cols, count=count.astype(cols["count"].dtype))
            return cols, keep

        monkeypatch.setattr(finalize, "host_epilogue", planted)
    result = rehearse()
    assert result["failed"] == 0
    assert not result["correct"], result["checks"]
