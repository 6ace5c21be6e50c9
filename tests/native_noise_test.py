"""Native secure-noise library tests.

Build + load + distribution cross-checks: the discrete samplers must match
their continuous targets at the configured granularity (the granularity is
~2^-40 relative, far below any statistical test's resolution), return exact
granularity multiples, and reject bad parameters. Role parity:
/root/reference/tests/dp_computations_test.py test_secure_laplace_noise_is_used
(the reference verifies C++ noise is wired; here the C++ lives in-repo).
"""

import ctypes
import os
import select
import signal
import threading
import warnings

import numpy as np
import pytest
from scipy import stats

from pipelinedp_tpu import noise_core
from pipelinedp_tpu.native import loader


@pytest.fixture(scope="module")
def lib():
    # install() (not just load()): earlier test files may have routed
    # sampling to the seedable fallback via seed_fallback_rng.
    if not loader.install():
        pytest.skip("native library unavailable (no compiler)")
    return loader.load()


class TestNativeSamplers:

    def test_loader_installed_into_noise_core(self, lib):
        assert loader.is_loaded()
        assert noise_core.using_native_sampling()

    def test_laplace_distribution(self, lib):
        scale = 3.0
        s = noise_core.sample_laplace(scale, (200_000,))
        # KS against the continuous Laplace: the 2^-40-relative granularity
        # is invisible at this sample size.
        _, p = stats.kstest(s, stats.laplace(scale=scale).cdf)
        assert p > 1e-4
        assert abs(s.std() / (scale * np.sqrt(2)) - 1) < 0.02

    def test_gaussian_distribution(self, lib):
        stddev = 7.5
        s = noise_core.sample_gaussian(stddev, (200_000,))
        _, p = stats.kstest(s, stats.norm(scale=stddev).cdf)
        assert p > 1e-4
        assert abs(s.std() / stddev - 1) < 0.02

    def test_granularity_multiples(self, lib):
        for scale in (0.1, 17.0, 1e6):
            g = noise_core.laplace_granularity(scale)
            s = noise_core.sample_laplace(scale, (1000,))
            np.testing.assert_array_equal(np.round(s / g) * g, s)

    def test_scalar_sampling(self, lib):
        out = noise_core.sample_laplace(2.0)
        assert isinstance(out, float)

    def test_not_replayable(self, lib):
        # Secure noise must differ across draws (no seeding surface).
        a = noise_core.sample_laplace(1.0, (100,))
        b = noise_core.sample_laplace(1.0, (100,))
        assert not np.array_equal(a, b)

    def test_invalid_parameters_rejected(self, lib):
        out = np.empty(1, dtype=np.int64)
        ptr = out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
        assert lib.pdp_sample_discrete_laplace(ptr, 1, 0.0) != 0
        assert lib.pdp_sample_discrete_laplace(ptr, 1, float("nan")) != 0
        assert lib.pdp_sample_discrete_gaussian(ptr, 1, -1.0) != 0

    def test_uniform_distribution(self, lib):
        s = noise_core.sample_uniform((200_000,))
        _, p = stats.kstest(s, stats.uniform().cdf)
        assert p > 1e-4
        assert (s >= 0).all() and (s < 1).all()

    def test_uniform_scalar(self, lib):
        u = noise_core.sample_uniform()
        assert isinstance(u, float)
        assert 0.0 <= u < 1.0

    def test_selection_draws_not_replayable(self, lib):
        # Keep decisions must come from the secure source: with no seed
        # installed, two identical selection batches at keep probability
        # ~1/2 per partition must not agree everywhere.
        from pipelinedp_tpu import partition_selection as ps
        ps.seed_rng(None)
        strategy = ps.TruncatedGeometricPartitionSelection(
            epsilon=1.0, delta=1e-5, max_partitions_contributed=1)
        counts = np.full(2000, int(strategy.threshold))
        keep_a, _ = strategy.select_vec(counts)
        keep_b, _ = strategy.select_vec(counts)
        assert not np.array_equal(keep_a, keep_b)
        # And the draw itself rides the native sampler, not numpy.
        assert noise_core.using_native_sampling()

    def test_exponential_mechanism_secure_draw(self, lib):
        from pipelinedp_tpu import dp_computations

        class Flat(dp_computations.ExponentialMechanism.ScoringFunction):
            def score(self, k):
                return 0.0

            @property
            def global_sensitivity(self):
                return 1.0

            @property
            def is_monotonic(self):
                return True

        dp_computations.ExponentialMechanism.seed_rng(None)
        mech = dp_computations.ExponentialMechanism(Flat())
        draws = {mech.apply(1.0, list(range(50))) for _ in range(300)}
        # Uniform over 50 candidates: 300 draws hit many distinct ones.
        assert len(draws) > 20

    def test_add_noise_array_uses_float64(self, lib):
        values = np.arange(1000, dtype=np.float32)
        out = noise_core.add_laplace_noise_array(values, 0.5)
        assert out.dtype == np.float64
        assert abs((out - values).mean()) < 0.2

    def test_engine_secure_path_end_to_end(self, lib):
        # The default JaxDPEngine path releases native noise.
        import pipelinedp_tpu as pdp
        rng = np.random.default_rng(0)
        accountant = pdp.NaiveBudgetAccountant(1.0, 1e-6)
        engine = pdp.JaxDPEngine(accountant)  # secure_host_noise=True
        params = pdp.AggregateParams(
            metrics=[pdp.Metrics.COUNT],
            max_partitions_contributed=2,
            max_contributions_per_partition=1)
        result = engine.aggregate(
            pdp.ColumnarData(pid=rng.integers(0, 3000, 10_000),
                             pk=rng.integers(0, 10, 10_000)),
            params, public_partitions=list(range(10)))
        accountant.compute_budgets()
        counts = result.to_columns()["count"]
        assert np.isfinite(counts).all()
        # Values are granularity multiples of the calibrated scale
        # (scale = l0 * linf / eps = 2 / 1.0 after the full-budget split).
        scale = 2 / 1.0
        g = noise_core.laplace_granularity(scale)
        np.testing.assert_allclose(np.round(counts / g) * g, counts,
                                   atol=1e-9)


# secure_noise.cc's kBlock: draws longer than one block split across threads.
BLOCK = 4096
SIZES = (1, BLOCK - 1, BLOCK, 17_770, 59_047, 200_000)
KINDS = ("laplace", "gaussian", "uniform")


def _width():
    return min(len(os.sched_getaffinity(0)), 16)


def _native_draw(lib, kind, n):
    """(values, target distribution) of one native draw of n into a
    zero-filled buffer, so a slice no worker wrote stays all zeros."""
    if kind == "uniform":
        out = np.zeros(n, dtype=np.float64)
        ptr = out.ctypes.data_as(ctypes.POINTER(ctypes.c_double))
        assert lib.pdp_sample_uniform_double(ptr, n) == 0
        return out, stats.uniform()
    out = np.zeros(n, dtype=np.int64)
    ptr = out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    if kind == "laplace":
        scale = 3.0
        g = noise_core.laplace_granularity(scale)
        assert lib.pdp_sample_discrete_laplace(ptr, n, scale / g) == 0
        return out * g, stats.laplace(scale=scale)
    stddev = 7.5
    g = noise_core.gaussian_granularity(stddev)
    assert lib.pdp_sample_discrete_gaussian(ptr, n, stddev / g) == 0
    return out * g, stats.norm(scale=stddev)


def _check_distribution(values, dist):
    # KS and the standard deviation at ~7 standard errors: the test makes
    # ~150 such checks per run, and a block of zeros fails both.
    _, p = stats.kstest(values, dist.cdf)
    assert p > 1e-6
    assert abs(values.std() / dist.std() - 1) < 8 / np.sqrt(len(values))


class TestBlockSplit:
    """Draws split into blocks over host threads (secure_noise.cc's
    ParallelFill): every block is a full draw of the distribution, from
    entropy no other block or call shares."""

    @pytest.mark.parametrize("n", SIZES)
    def test_threads_for(self, lib, n):
        threads = lib.pdp_noise_threads_for(n)
        assert 1 <= threads <= _width()
        assert threads == min(-(-n // BLOCK), _width())
        if n <= BLOCK:
            assert threads == 1

    def test_one_value_past_a_block_splits(self, lib):
        assert lib.pdp_noise_threads_for(BLOCK + 1) == min(2, _width())

    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("n", SIZES)
    def test_whole_draw_and_each_block_match_the_distribution(
            self, lib, kind, n):
        values, dist = _native_draw(lib, kind, n)
        _check_distribution(values, dist)
        for s in range(0, n, BLOCK):
            _check_distribution(values[s:s + BLOCK], dist)

    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("n", SIZES)
    def test_blocks_and_calls_never_repeat(self, lib, kind, n):
        first, _ = _native_draw(lib, kind, n)
        second, _ = _native_draw(lib, kind, n)
        assert not np.array_equal(first, second)
        full = [first[s:s + BLOCK].tobytes()
                for s in range(0, n - BLOCK + 1, BLOCK)]
        assert len(set(full)) == len(full)

    def test_concurrent_callers_share_the_pool(self, lib):
        # More calling threads than CPUs, each making split draws: the
        # pool serves one draw at a time and every call must finish with
        # every block written.
        n, rounds = 17_770, 5
        results, errors = [], []

        def caller():
            try:
                for _ in range(rounds):
                    values, dist = _native_draw(lib, "gaussian", n)
                    for s in range(0, n, BLOCK):
                        assert np.count_nonzero(values[s:s + BLOCK]) > 0
                    results.append(values.tobytes())
            except AssertionError as e:
                errors.append(e)

        threads = [threading.Thread(target=caller)
                   for _ in range(2 * _width() + 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        assert not errors
        assert len(set(results)) == len(threads) * rounds

    def test_forked_child_draws_fresh_entropy(self, lib):
        # The child inherits the forking thread's buffered entropy and
        # none of the pool's threads: its draws must neither replay the
        # parent's next values nor wait on a pool that is gone.
        _native_draw(lib, "laplace", 8)  # buffers entropy on this thread
        _native_draw(lib, "laplace", 59_047)  # starts the pool
        read_fd, write_fd = os.pipe()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # fork under JAX's threads
            pid = os.fork()
        if pid == 0:
            try:
                small, _ = _native_draw(lib, "laplace", 8)
                big, _ = _native_draw(lib, "laplace", 59_047)
                os.write(write_fd, small.tobytes() + big[:8].tobytes())
            finally:
                os._exit(0)
        os.close(write_fd)
        small, _ = _native_draw(lib, "laplace", 8)
        big, _ = _native_draw(lib, "laplace", 59_047)
        ready, _, _ = select.select([read_fd], [], [], 60)
        if not ready:
            os.kill(pid, signal.SIGKILL)
        os.waitpid(pid, 0)
        assert ready, "the forked child's draws did not finish"
        data = os.read(read_fd, 128)
        os.close(read_fd)
        assert len(data) == 128
        assert not np.array_equal(np.frombuffer(data[:64]), small)
        assert not np.array_equal(np.frombuffer(data[64:]), big[:8])
