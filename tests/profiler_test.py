"""Profiler hooks: traces capture the engine's named stages."""

import glob
import os

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import profiler
from pipelinedp_tpu.obs import trace as trace_lib


class TestProfiler:

    def test_stage_is_noop_without_trace(self):
        with profiler.stage("anything"):
            x = 1 + 1
        assert x == 2

    def test_profile_captures_engine_trace(self, tmp_path):
        logdir = str(tmp_path / "trace")
        rng = np.random.default_rng(0)
        data = pdp.ColumnarData(pid=rng.integers(0, 100, 2000),
                                pk=rng.integers(0, 5, 2000),
                                value=rng.uniform(0, 1, 2000))
        params = pdp.AggregateParams(metrics=[pdp.Metrics.COUNT],
                                     max_partitions_contributed=5,
                                     max_contributions_per_partition=50)
        with profiler.profile(logdir):
            accountant = pdp.NaiveBudgetAccountant(1.0, 1e-6)
            engine = pdp.JaxDPEngine(accountant)
            result = engine.aggregate(data, params,
                                      public_partitions=list(range(5)))
            accountant.compute_budgets()
            result.to_columns()
        traces = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                           recursive=True)
        assert traces, f"no trace files under {logdir}"


class TestThreadSafety:
    """Stage timers and event counters under concurrent recording — the
    encode/prefetch worker pools record from pool threads into the main
    thread's collectors (ISSUE 5 satellite: lock + thread-local sinks)."""

    def test_stage_time_hammer_no_lost_updates(self):
        import threading

        n_threads, n_iters = 8, 5_000
        with profiler.collect_stage_times() as sink:
            sinks = profiler.current_sinks()

            def worker():
                for _ in range(n_iters):
                    trace_lib._add_stage_time(sinks, "hammer", 1.0)

            threads = [threading.Thread(target=worker)
                       for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        # Exactly one update per call: lost updates would undercount.
        assert sink["hammer"] == float(n_threads * n_iters)

    def test_event_count_hammer(self):
        import threading

        profiler.reset_events("test/")
        n_threads, n_iters = 8, 5_000

        def worker():
            for _ in range(n_iters):
                profiler.count_event("test/hammer")

        threads = [threading.Thread(target=worker)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert profiler.event_count("test/hammer") == n_threads * n_iters
        profiler.reset_events("test/")

    def test_adopt_sinks_merges_worker_stages(self):
        import threading

        with profiler.collect_stage_times() as sink:
            parent_sinks = profiler.current_sinks()

            def worker():
                with profiler.adopt_sinks(parent_sinks):
                    with profiler.stage("worker_stage"):
                        pass

            t = threading.Thread(target=worker)
            t.start()
            t.join()
            # The worker thread's stage landed in the parent's sink; the
            # worker's thread-local state was restored on exit.
            assert "worker_stage" in sink
            assert sink["worker_stage"] >= 0.0

    def test_adopt_sinks_restores_previous(self):
        with profiler.collect_stage_times() as outer:
            with profiler.adopt_sinks([{}]):
                pass
            with profiler.stage("after_adopt"):
                pass
        assert "after_adopt" in outer


class TestDebugLocks:
    """PIPELINEDP_TPU_DEBUG_LOCKS=1 asserts the sink lock around every
    sink mutation (validated through native.loader.env_int)."""

    def test_debug_locks_assertion_passes_on_locked_path(self, monkeypatch):
        monkeypatch.setenv(trace_lib.DEBUG_LOCKS_ENV, "1")
        with profiler.collect_stage_times() as sink:
            with profiler.stage("debug_locks_stage"):
                pass
            trace_lib._add_stage_time(profiler.current_sinks(),
                                      "direct", 0.5)
        assert "debug_locks_stage" in sink
        assert sink["direct"] == 0.5

    def test_debug_locks_off_by_default(self, monkeypatch):
        monkeypatch.delenv(trace_lib.DEBUG_LOCKS_ENV, raising=False)
        assert trace_lib._debug_locks() is False

    def test_debug_locks_env_is_validated(self, monkeypatch):
        monkeypatch.setenv(trace_lib.DEBUG_LOCKS_ENV, "banana")
        with pytest.raises(ValueError, match="DEBUG_LOCKS"):
            trace_lib._debug_locks()
        monkeypatch.setenv(trace_lib.DEBUG_LOCKS_ENV, "7")
        with pytest.raises(ValueError, match=r"\[0, 1\]"):
            trace_lib._debug_locks()
