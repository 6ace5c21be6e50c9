"""The plain reference against the program's own formulas, and the plain
release against the reference's expectations."""

import math

import numpy as np
import pytest

from benchmark import reference

QUERY = {"metrics": ["COUNT", "SUM", "PRIVACY_ID_COUNT"],
         "noise_kind": "LAPLACE", "epsilon": 1.0, "delta": 1e-6,
         "max_partitions_contributed": 3,
         "max_contributions_per_partition": 2,
         "min_value": 0.0, "max_value": 5.0}


def columns(n_rows=200_000, n_partitions=500, n_users=20_000, seed=3):
    """Uniform privacy units, power-law partitions pk = floor(P * u**4),
    stars 1..5."""
    rng = np.random.default_rng(seed)
    pid = rng.integers(0, n_users, n_rows, dtype=np.int32)
    pk = np.minimum(n_partitions * rng.random(n_rows)**4,
                    n_partitions - 1).astype(np.int32)
    value = rng.integers(1, 6, n_rows).astype(np.float32)
    return pid, pk, value


@pytest.mark.parametrize("noise", ["LAPLACE", "GAUSSIAN"])
def test_noise_matches_the_program_formulas(noise):
    from pipelinedp_tpu import dp_computations, noise_core
    q = reference.Query.from_dict(dict(QUERY, noise_kind=noise))
    (eps, delta), _ = reference.budgets(q)
    assert eps == pytest.approx(0.25)
    sd = reference.noise_sd(q)
    for metric, linf in (("COUNT", 2), ("SUM", 10), ("PRIVACY_ID_COUNT", 1)):
        if noise == "LAPLACE":
            want = math.sqrt(2) * noise_core.laplace_diversity(
                eps, dp_computations.compute_l1_sensitivity(3, linf))
        else:
            want = noise_core.analytic_gaussian_sigma(
                eps, delta, dp_computations.compute_l2_sensitivity(3, linf))
        assert sd[metric] == pytest.approx(want, rel=1e-9)


def test_keep_probability_matches_truncated_geometric():
    from pipelinedp_tpu import partition_selection as ps
    q = reference.Query.from_dict(QUERY)
    _, (eps, delta) = reference.budgets(q)
    pi = reference.keep_probability(q, 600)
    strategy = ps.TruncatedGeometricPartitionSelection(eps, delta, 3)
    want = strategy.probability_of_keep_vec(np.arange(1, 601))
    np.testing.assert_allclose(pi[1:], want, rtol=1e-9, atol=1e-15)
    assert pi[0] == 0.0


def test_expectation_matches_the_plain_release_on_average():
    pairs = reference.Pairs(*columns())
    q = reference.Query.from_dict(QUERY)
    e = reference.expect(pairs, q)
    rng = np.random.default_rng(0)
    kept = [len(reference.release(pairs, q, rng).keys) for _ in range(20)]
    sd = math.sqrt((e.keep * (1 - e.keep)).sum() / 20)
    assert abs(np.mean(kept) - e.keep.sum()) < 4 * sd


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_plain_release_is_judged_sound(seed):
    pairs = reference.Pairs(*columns())
    q = reference.Query.from_dict(QUERY)
    e = reference.expect(pairs, q)
    judge = reference.Judge()
    judge.add(e, reference.release(pairs, q, np.random.default_rng(seed)))
    r = judge.readings()
    # ~80 partitions x 3 metrics of Laplace scores: the RMS of the scores
    # has a standard deviation of about 0.07 at this size.
    assert r["z_max"] < 6 and r["z_sd_gap"] < 0.3 and r["kept_gap"] < 4


def test_control_without_bounding_is_judged_unsound():
    pairs = reference.Pairs(*columns())
    q = reference.Query.from_dict(QUERY)
    e = reference.expect(pairs, q)
    judge = reference.Judge()
    judge.add(e, reference.release(pairs, q, np.random.default_rng(0),
                                   bound=False))
    r = judge.readings()
    assert r["z_max"] > 30 and r["z_sd_gap"] > 1 and r["kept_gap"] > 10


def test_judge_refuses_partitions_not_in_the_data_or_released_twice():
    pid, pk, value = columns(n_rows=10_000, n_partitions=50, n_users=1000)
    q = reference.Query.from_dict(QUERY)
    e = reference.expect(reference.Pairs(pid, pk, value), q)
    vals = {m: np.zeros(1) for m in q.metrics}
    with pytest.raises(ValueError, match="not in the data"):
        reference.Judge().add(e, reference.Release(np.array([10**6]), vals))
    two = {m: np.zeros(2) for m in q.metrics}
    with pytest.raises(ValueError, match="twice"):
        reference.Judge().add(e, reference.Release(np.array([0, 0]), two))


def test_verdict_needs_every_release_compared():
    limits = {"z_max": 10.0}
    assert reference.verdict({"z_max": 3.0}, limits, 2, 2)
    assert not reference.verdict({"z_max": 3.0}, limits, 3, 2)
    assert not reference.verdict({"z_max": 3.0}, limits, 0, 0)
    assert not reference.verdict({"z_max": 11.0}, limits, 2, 2)
