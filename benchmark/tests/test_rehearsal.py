"""Each cell rehearsed on the CPU at a small size through the rest of a
run (``run.run_cell``: data, driver set-up, window, judgement, metrics),
skipping only the look for a chip; then the same runs with the control
in the program's place and with faults planted underneath the timed
path, each of which must come out not correct.

The small configurations keep each cell's DP parameters and data law and
make the partitions large next to the noise, as they are at full size,
so that a fault shows in the scores as it would on the chip. They release
a few hundred partitions, not tens of thousands, so their scores spread
wider than a cell's: the limits here are set for this size (sound
rehearsals on twelve seeds read up to z_max 4.3, z_sd_gap 0.07,
z_sd_gap_top 0.11, kept_gap 1.6; the faults at least 0.2 on one of
them).
"""

import copy
import time

import numpy as np
import pytest

from benchmark import common, reference, run

BENCH = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
SMALL_DATA = {
    "netflix_prize": {"n_rows": 600_000, "n_partitions": 300,
                      "n_users": 30_000, "user_id_max": 90_000,
                      "user_rows": {"median": 10, "max": 300},
                      "movie_rows": {"median": 1000, "min": 3,
                                     "max": 20_000}},
}
SECONDS = 1.0
TEST_LIMITS = {"z_max": 12.0, "z_sd_gap": 0.12, "z_sd_gap_top": 0.2,
               "kept_gap": 8.0}


def small_cell(name: str) -> run.Cell:
    import jax
    cell = run.resolve(BENCH, name, "TPU v5 lite")
    cfg = copy.deepcopy(cell.config)
    cfg["data"].update(SMALL_DATA[cfg["generator"]])
    return run.Cell(workload=cell.workload, config=cfg,
                    traffic=cell.traffic, limits=TEST_LIMITS,
                    peaks=cell.peaks), jax.devices()[:1]


def rehearse(name: str, seed: int = 2**31 + 11) -> dict:
    cell, devices = small_cell(name)
    defs = [m for m in BENCH["end_to_end"] + BENCH["per_layer"]
            if run.applies(m, name) and m["source"] != "device_trace"]
    return run.run_cell(cell, defs, seed=seed, seconds=SECONDS,
                        trace=False, devices=devices,
                        t_start=time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    result = rehearse(name)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    wanted = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if run.applies(m, name) and m["source"] != "device_trace"}
    assert set(result["metrics"]) == wanted
    assert result["device"]["count"] == 1


@pytest.fixture
def control(monkeypatch):
    """The plain reference in the program's place, without contribution
    bounding."""
    rng = np.random.default_rng(5)
    tables = {}

    def plain(data, q):
        key = id(data)
        if key not in tables:
            tables[key] = reference.Pairs(data.pid, data.pk, data.value)
        return reference.release(tables[key], reference.Query.from_dict(q),
                                 rng, bound=False)

    monkeypatch.setattr(common, "release_of_aggregate",
                        lambda data, q, seed: plain(data, q))


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, control):
    assert not rehearse(name)["correct"]


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


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["answer_altered"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch):
    from pipelinedp_tpu.ops import finalize
    original = finalize.host_epilogue
    rng = np.random.default_rng(7)

    def planted(plan, scalars, accs, vector_sums):
        if fault in FAULTS:
            accs = FAULTS[fault](accs, rng)
        cols, keep = original(plan, scalars, accs, vector_sums)
        if fault == "answer_altered":
            # The most popular released partition's count, produced
            # wrong: halved.
            count = np.array(cols["count"], dtype=np.float64)
            top = int(np.argmax(np.where(keep, count, -np.inf)))
            count[top] *= 0.5
            cols = dict(cols, count=count.astype(cols["count"].dtype))
        return cols, keep

    monkeypatch.setattr(finalize, "host_epilogue", planted)
    assert not rehearse(name)["correct"]
