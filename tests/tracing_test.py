"""The one span system: profiler annotation, stage sinks and tracer share
one span call and one clock; the slab driver's child spans and wire
counters; the compile listener; released bits unchanged by tracing."""

import glob
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import profiler, runtime
from pipelinedp_tpu.native import loader
from pipelinedp_tpu.obs import metrics as metrics_lib
from pipelinedp_tpu.obs import trace as trace_lib
from pipelinedp_tpu.ops import streaming, wirecodec
from pipelinedp_tpu.parallel import sharded
from pipelinedp_tpu.runtime import driver as driver_lib

from benchmark.metrics import _stages

N_ROWS = 6_000


@pytest.fixture
def tracer():
    t = trace_lib.install(trace_lib.Tracer())
    try:
        yield t
    finally:
        trace_lib.shutdown()


def _data(n=N_ROWS, seed=3):
    rng = np.random.default_rng(seed)
    return pdp.ColumnarData(
        pid=rng.integers(10, 900, n).astype(np.int64),
        pk=rng.integers(0, 25, n).astype(np.int32),
        value=rng.integers(1, 6, n).astype(np.float32))


def _params():
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM,
                 pdp.Metrics.PRIVACY_ID_COUNT],
        max_partitions_contributed=2,
        max_contributions_per_partition=1,
        min_value=1.0, max_value=5.0)


def _aggregate(data, mesh=None, **engine_kw):
    accountant = pdp.NaiveBudgetAccountant(1.0, 1e-6)
    engine = pdp.JaxDPEngine(accountant, seed=7, mesh=mesh,
                             stream_chunks=4, secure_host_noise=False,
                             **engine_kw)
    result = engine.aggregate(data, _params())
    accountant.compute_budgets()
    return result.to_columns()


def _xplane_host_events(trace_dir):
    """{name: [start ns since the Unix epoch]} of the host plane's
    events: the trace's profile_start_time plus each event's start."""
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    prof = jax.profiler.ProfileData.from_file(path)
    start = dict(prof.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    out = {}
    for line in prof.find_plane_with_name("/host:CPU").lines:
        for ev in line.events:
            out.setdefault(ev.name, []).append(start + int(ev.start_ns))
    return out


class TestOneClock:

    def test_chrome_export_and_xplane_agree_within_1ms(self, tracer,
                                                       tmp_path):
        trace_dir = str(tmp_path / "xplane")
        jax.profiler.start_trace(trace_dir)
        try:
            with trace_lib.span("dp/clock_probe", attempt=0):
                with trace_lib.span("dp/clock_probe_child"):
                    jnp.ones(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        host = _xplane_host_events(trace_dir)
        doc = tracer.export_chrome()
        chrome = {e["name"]: e for e in doc["traceEvents"]
                  if e["ph"] == "X"}
        # Every span the tracer recorded is on the host plane under the
        # same name, starting within 1 ms of its Chrome-export event.
        assert set(chrome) == {"dp/clock_probe", "dp/clock_probe_child"}
        for name, ev in chrome.items():
            (xplane_ns,) = host[name]
            assert abs(xplane_ns - ev["ts"] * 1000.0) < 1e6, name

    def test_stage_delegate_feeds_sinks_and_tracer(self, tracer):
        with profiler.collect_stage_times() as stages:
            with profiler.stage("dp/outer"):
                with trace_lib.span("dp/inner", chunk=3):
                    pass
        assert set(stages) == {"dp/outer", "dp/inner"}
        assert stages["dp/outer"] >= stages["dp/inner"] >= 0.0
        inner, outer = tracer.spans()
        assert (inner.name, outer.name) == ("dp/inner", "dp/outer")
        assert inner.parent_id == outer.span_id
        assert inner.attrs == {"chunk": 3}


class TestChildSpans:
    """A small cold aggregate under collect_stage_times: every child
    span appears, nested within its parent, and the benchmark's
    host-encode folding still reads the top-level names alone."""

    TOP_LEVEL = ("dp/encode", "dp/wire_prep", "dp/wire_sort_upfront",
                 "dp/wire_encode")

    def _stages(self, **engine_kw):
        with profiler.collect_stage_times() as stages:
            _aggregate(_data(), **engine_kw)
        return dict(stages)

    def test_every_child_appears_and_nests(self):
        stages = self._stages()
        native = wirecodec._load_packer() is not None
        wanted = {"dp/encode/pk", "dp/encode/pid", "dp/encode/value",
                  "dp/wire_prep/pid_range", "dp/wire_prep/value_plan",
                  "dp/slab_wait", "dp/slab_transfer", "dp/slab_dispatch",
                  "dp/finalize", "dp/free_columns"}
        wanted |= ({"dp/wire_prep/native_scan", "dp/wire_emit"} if native
                   else {"dp/wire_prep/fallback_stats"})
        assert wanted <= set(stages), sorted(wanted - set(stages))
        assert stages["dp/encode"] >= sum(
            stages[f"dp/encode/{c}"] for c in ("pk", "pid", "value"))
        assert stages["dp/wire_prep"] >= sum(
            v for k, v in stages.items() if k.startswith("dp/wire_prep/"))
        slabs = sum(v for k, v in stages.items()
                    if k.startswith("dp/stream_slab_"))
        assert slabs >= stages["dp/slab_transfer"] + stages["dp/slab_wait"]

    def test_host_encode_folding_reads_top_level_names_alone(self):
        stages = self._stages()
        for name in stages:
            if name.startswith("dp/stream_slab_"):
                assert name[len("dp/stream_slab_"):].isdigit(), name
        alone = (sum(stages.get(k, 0.0) for k in self.TOP_LEVEL)
                 + sum(v for k, v in stages.items()
                       if k.startswith("dp/stream_slab_")))
        assert _stages.host_encode_s(stages) == pytest.approx(alone)
        with_children = dict(stages, **{
            "dp/wire_prep/native_scan": 100.0, "dp/slab_wait": 100.0,
            "dp/encode/pk": 100.0, "dp/wire_emit": 100.0})
        assert _stages.host_encode_s(with_children) == pytest.approx(alone)

    def test_checkpoint_and_watchdog_spans(self, tmp_path):
        policy = runtime.CheckpointPolicy(
            store=runtime.InMemoryCheckpointStore(), run_id="spans")
        stages = self._stages(checkpoint_policy=policy,
                              watchdog_timeout_s=60.0)
        assert {"dp/checkpoint", "dp/slab_sync"} <= set(stages)

    @pytest.mark.parametrize("n_rows,want", [(70_000, 4), (60_000, 1)])
    def test_native_scan_records_its_width(self, tracer, monkeypatch,
                                           n_rows, want):
        # The prep scan splits over the pool from 2^16 rows on; below
        # that it stays on one thread whatever the forced width.
        if wirecodec._load_packer() is None:
            pytest.skip("native packer unavailable")
        monkeypatch.setenv(loader.ENCODE_THREADS_ENV, "4")
        _aggregate(_data(n=n_rows))
        (scan,) = [sp for sp in tracer.spans()
                   if sp.name == "dp/wire_prep/native_scan"]
        assert scan.attrs["threads"] == want
        parent = {sp.span_id: sp.name for sp in tracer.spans()}
        assert parent[scan.parent_id] == "dp/wire_prep"


class TestServeSpans:
    """The query plane's spans under ``serving/query_batch``: planning,
    the batched replay, the lanes' slicing, and each config's epilogue
    on the executor thread."""

    L0 = (1, 2, 4)

    @pytest.fixture
    def spans(self, tracer):
        from pipelinedp_tpu import serving
        params = _params()
        configs = [serving.QueryConfig(
            metrics=params.metrics, epsilon=1.0, delta=1e-6,
            max_partitions_contributed=l0, max_contributions_per_partition=1,
            min_value=1.0, max_value=5.0, seed=20 + l0) for l0 in self.L0]
        with serving.DatasetSession(_data(), n_chunks=4,
                                    name="serve-spans") as session:
            with profiler.collect_stage_times() as stages:
                session.query_batch(configs)
        spans = tracer.spans()
        (batch,) = [sp for sp in spans if sp.name == "serving/query_batch"]
        return spans, batch, dict(stages), threading.get_ident()

    @staticmethod
    def _named(spans, name):
        return [sp for sp in spans if sp.name == name]

    @pytest.mark.parametrize("name,count", [
        ("dp/serve/plan", 1), ("dp/serve/replay", 1),
        ("dp/serve/lane_slice", 1), ("dp/serve/epilogue", len(L0))])
    def test_each_span_is_a_child_of_query_batch(self, spans, name, count):
        spans, batch, stages, _ = spans
        found = self._named(spans, name)
        assert len(found) == count
        assert all(sp.parent_id == batch.span_id for sp in found)
        assert name in stages

    def test_replay_and_lane_slice_record_their_width(self, spans):
        spans, _, _, _ = spans
        (replay,) = self._named(spans, "dp/serve/replay")
        assert replay.attrs == {"width": len(self.L0), "n_chunks": 4}
        (lanes,) = self._named(spans, "dp/serve/lane_slice")
        assert lanes.attrs == {"width": len(self.L0)}
        (inner,) = self._named(spans, "serving/replay_batched")
        assert inner.parent_id == replay.span_id

    def test_epilogue_runs_on_the_executor_with_lane_and_l0(self, spans):
        spans, _, _, main = spans
        epilogues = self._named(spans, "dp/serve/epilogue")
        assert sorted((sp.attrs["lane"], sp.attrs["l0"])
                      for sp in epilogues) == list(enumerate(self.L0))
        assert all(sp.thread_id != main for sp in epilogues)
        ids = {sp.span_id for sp in epilogues}
        finalizes = self._named(spans, "dp/finalize")
        assert len(finalizes) == len(self.L0)
        assert all(sp.parent_id in ids for sp in finalizes)


def _counter_deltas(fn):
    names = (driver_lib.EVENT_WIRE_BYTES, driver_lib.EVENT_WIRE_ROWS)
    before = {n: profiler.event_count(n) for n in names}
    out = fn()
    return out, {n: profiler.event_count(n) - before[n] for n in names}


class TestWireCounters:

    @pytest.mark.parametrize("topology", ["single_device", "mesh8"])
    def test_bytes_and_rows_of_every_shipped_slab(self, topology,
                                                  monkeypatch):
        placement_cls = (sharded._MeshPlacement if topology == "mesh8"
                         else streaming._SingleDevicePlacement)
        shipped = []
        original = placement_cls.transfer

        def spy(self, slab, s0, s1):
            shipped.append(int(slab.nbytes))
            return original(self, slab, s0, s1)

        monkeypatch.setattr(placement_cls, "transfer", spy)
        mesh = sharded.make_mesh(8) if topology == "mesh8" else None
        data = _data()
        _, moved = _counter_deltas(lambda: _aggregate(data, mesh=mesh))
        assert shipped
        assert moved[driver_lib.EVENT_WIRE_BYTES] == sum(shipped)
        assert moved[driver_lib.EVENT_WIRE_ROWS] == N_ROWS

    def test_single_shot_ships_its_columns_as_one_slab(self):
        data = _data()
        accountant = pdp.NaiveBudgetAccountant(1.0, 1e-6)
        engine = pdp.JaxDPEngine(accountant, seed=7, stream_chunks=1,
                                 secure_host_noise=False)
        with profiler.collect_stage_times() as stages:
            result = engine.aggregate(data, _params())
            accountant.compute_budgets()
            _, moved = _counter_deltas(result.to_columns)
        # int32 pid and pk, float32 value.
        assert moved[driver_lib.EVENT_WIRE_BYTES] == N_ROWS * 12
        assert moved[driver_lib.EVENT_WIRE_ROWS] == N_ROWS
        assert "dp/slab_transfer" in stages
        assert not any(k.startswith("dp/stream_slab_") for k in stages)

    def test_device_resident_replay_counts_nothing(self):
        from pipelinedp_tpu import serving
        with serving.DatasetSession(_data(), n_chunks=4,
                                    name="wire-count") as session:
            session._wire.ensure_device()
            _, moved = _counter_deltas(lambda: session.query(
                _params(), epsilon=1.0, delta=1e-6, seed=1,
                secure_host_noise=False).to_columns())
        assert moved == {driver_lib.EVENT_WIRE_BYTES: 0,
                         driver_lib.EVENT_WIRE_ROWS: 0}

    def test_counters_in_metrics_exports(self):
        _aggregate(_data())
        jax.jit(lambda x: x - 2)(jnp.ones(5)).block_until_ready()
        reg = metrics_lib.default_registry()
        prom = reg.to_prometheus()
        events = reg.snapshot()["events"]
        for name in (driver_lib.EVENT_WIRE_BYTES, driver_lib.EVENT_WIRE_ROWS,
                     profiler.EVENT_COMPILES):
            assert f'event="{name}"' in prom
            assert events[name] > 0


class TestCompileListener:

    def test_fresh_jit_counts_one_compile_by_name(self):
        def tracing_test_fresh_program(x):
            return x * 3 + 1

        x = jnp.ones(3)
        x.block_until_ready()
        hist = metrics_lib.compile_seconds()
        label = {"program": "jit(tracing_test_fresh_program)"}
        before = profiler.event_count(profiler.EVENT_COMPILES)
        count0 = hist.snapshot(**label)["count"]
        jax.jit(tracing_test_fresh_program)(x).block_until_ready()
        assert profiler.event_count(profiler.EVENT_COMPILES) == before + 1
        snap = hist.snapshot(**label)
        assert snap["count"] == count0 + 1 and snap["sum"] > 0
        prom = metrics_lib.default_registry().to_prometheus()
        assert ('pipelinedp_tpu_compile_seconds_count'
                '{program="jit(tracing_test_fresh_program)"} 1') in prom


class TestBitIdentity:

    def test_batch_release_identical_with_trace_and_tracer(self, tmp_path):
        data = _data()
        trace_lib.shutdown()
        off = _aggregate(data)
        tracer = trace_lib.install(trace_lib.Tracer())
        jax.profiler.start_trace(str(tmp_path / "xplane"))
        try:
            on = _aggregate(data)
        finally:
            jax.profiler.stop_trace()
            trace_lib.shutdown()
        assert tracer.spans()
        assert sorted(off) == sorted(on)
        for k in off:
            np.testing.assert_array_equal(np.asarray(off[k]),
                                          np.asarray(on[k]), err_msg=k)
        path = tracer.write_chrome(str(tmp_path / "spans.json"))
        names = {e["name"] for e in json.load(open(path))["traceEvents"]}
        assert {"dp/slab_transfer", "dp/wire_prep/pid_range"} <= names
