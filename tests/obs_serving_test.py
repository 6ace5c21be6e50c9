"""Integration tests: observability through the serving stack.

The PR-11 acceptance surface that needs a real engine:

  * released values are BIT-IDENTICAL with tracing enabled vs disabled
    — warm (session) and cold (engine) runs, single-device and mesh8;
  * one warm serving query with tracing enabled produces a loadable
    Chrome trace containing admission → replay → finalize spans, and a
    Prometheus exposition with a non-empty query-latency histogram;
  * the audit trail records every typed outcome (released / refunded /
    shed / deadline-expired / double-release-refused) with exact
    tenant-charge accounting alongside;
  * the no-private-leak scan: every span attribute, span event, metric
    label and audit field emitted by the full matrix above is a scalar
    with a non-forbidden key, and no raw pid/pk sentinel value ever
    appears in any record.
"""

import json
import os
import threading
import urllib.request

import numpy as np
import pytest

import pipelinedp_tpu as pdp
from pipelinedp_tpu import runtime, serving
from pipelinedp_tpu.obs import flight as flight_lib
from pipelinedp_tpu.obs import metrics as metrics_lib
from pipelinedp_tpu.obs import trace as trace_lib
from pipelinedp_tpu.parallel import sharded

from tests.obs_test import validate_trace_schema

N_ROWS = 30_000
N_PARTITIONS = 200
# Sentinel privacy ids: values that appear nowhere else, so the leak
# scan can assert they never surface in any obs record.
PID_LO, PID_HI = 7_654_000, 7_654_000 + 3_000


def _data():
    rng = np.random.default_rng(11)
    return pdp.ColumnarData(
        pid=rng.integers(PID_LO, PID_HI, N_ROWS).astype(np.int64),
        pk=rng.integers(0, N_PARTITIONS, N_ROWS).astype(np.int32),
        value=rng.uniform(0, 5, N_ROWS).astype(np.float32))


def _params():
    return pdp.AggregateParams(
        metrics=[pdp.Metrics.COUNT, pdp.Metrics.SUM],
        max_partitions_contributed=8,
        max_contributions_per_partition=4,
        min_value=0.0,
        max_value=5.0)


@pytest.fixture
def tracer():
    t = trace_lib.install(trace_lib.Tracer())
    try:
        yield t
    finally:
        trace_lib.shutdown()


def _query_cols(session, seed=0, **kw):
    return session.query(_params(), epsilon=1.0, delta=1e-6, seed=seed,
                         secure_host_noise=False, **kw).to_columns()


def _assert_same_columns(a, b):
    assert sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


class TestOpsPlaneBitIdentity:
    """The PR-13 acceptance: released DP values are BIT-IDENTICAL with
    the full operational plane enabled (tracer + live ops endpoint +
    always-on flight recording + forced slow-query captures) vs
    everything disabled — warm and cold, single-device and mesh8."""

    @pytest.mark.parametrize("topology", ["single_device", "mesh8"])
    def test_warm_and_cold_bit_identical_with_plane_on(
            self, topology, tmp_path, monkeypatch):
        mesh = sharded.make_mesh(8) if topology == "mesh8" else None
        data = _data()

        def cold():
            accountant = pdp.NaiveBudgetAccountant(1.0, 1e-6)
            engine = pdp.JaxDPEngine(accountant, seed=5, mesh=mesh,
                                     stream_chunks=4,
                                     secure_host_noise=False)
            result = engine.aggregate(data, _params())
            accountant.compute_budgets()
            return result.to_columns()

        # Plane fully off.
        trace_lib.shutdown()
        monkeypatch.delenv(flight_lib.CAPTURE_DIR_ENV, raising=False)
        monkeypatch.delenv(flight_lib.SLOW_QUERY_ENV, raising=False)
        with serving.DatasetSession(data, n_chunks=4, mesh=mesh,
                                    name=f"plane-off-{topology}") as s:
            warm_off = _query_cols(s)
        cold_off = cold()

        # Plane fully on: tracer installed, ops endpoint live over a
        # manager, flight spool bound, every query captured.
        monkeypatch.setenv(flight_lib.CAPTURE_DIR_ENV,
                           str(tmp_path / "cap"))
        monkeypatch.setenv(flight_lib.SLOW_QUERY_ENV, "0.000001")
        trace_lib.install(trace_lib.Tracer())
        manager = serving.SessionManager(
            serving.SessionStore(str(tmp_path / "store")), ops_port=0)
        try:
            session = manager.create(f"plane-on-{topology}", data,
                                     n_chunks=4, mesh=mesh)
            warm_on = _query_cols(session)
            cold_on = cold()
            # The endpoint really is live while the bits are compared.
            status = urllib.request.urlopen(
                manager.ops_server.url + "/statusz", timeout=10).status
            assert status == 200
        finally:
            manager.close()
            trace_lib.shutdown()
        assert os.listdir(tmp_path / "cap"), "capture never triggered"
        _assert_same_columns(warm_off, warm_on)
        _assert_same_columns(cold_off, cold_on)


class TestBitIdentityOnOff:
    """Tracing must be observationally free: same released bits on and
    off, for warm (session) and cold (engine) paths."""

    @pytest.mark.parametrize("topology", ["single_device", "mesh8"])
    def test_warm_query_bit_identical(self, topology):
        mesh = sharded.make_mesh(8) if topology == "mesh8" else None
        data = _data()
        trace_lib.shutdown()
        with serving.DatasetSession(data, n_chunks=4, mesh=mesh,
                                    name=f"off-{topology}") as s_off:
            off = _query_cols(s_off)
        trace_lib.install(trace_lib.Tracer())
        try:
            with serving.DatasetSession(data, n_chunks=4, mesh=mesh,
                                        name=f"on-{topology}") as s_on:
                on = _query_cols(s_on)
                repeat = _query_cols(s_on)  # bound-cache hit leg
        finally:
            trace_lib.shutdown()
        _assert_same_columns(off, on)
        _assert_same_columns(off, repeat)

    @pytest.mark.parametrize("topology", ["single_device", "mesh8"])
    def test_cold_engine_bit_identical(self, topology):
        mesh = sharded.make_mesh(8) if topology == "mesh8" else None
        data = _data()

        def run():
            accountant = pdp.NaiveBudgetAccountant(1.0, 1e-6)
            engine = pdp.JaxDPEngine(accountant, seed=5, mesh=mesh,
                                     stream_chunks=4,
                                     secure_host_noise=False)
            result = engine.aggregate(data, _params())
            accountant.compute_budgets()
            return result.to_columns()

        trace_lib.shutdown()
        off = run()
        trace_lib.install(trace_lib.Tracer())
        try:
            on = run()
        finally:
            trace_lib.shutdown()
        _assert_same_columns(off, on)


class TestAcceptanceTraceAndExposition:
    """One warm serving query with tracing on -> loadable Chrome trace
    with admission/replay/finalize spans + non-empty query-latency
    Prometheus histogram."""

    def test_warm_query_trace_and_histogram(self, tracer, tmp_path):
        data = _data()
        trace_file = str(tmp_path / "query_trace.json")
        with serving.DatasetSession(data, n_chunks=4,
                                    name="accept") as session:
            before = metrics_lib.query_seconds().snapshot(
                outcome="released")["count"]
            _query_cols(session, trace_path=trace_file)

        doc = json.load(open(trace_file))
        events = doc["traceEvents"]
        names = {e["name"] for e in events if e["ph"] == "X"}
        assert {"serving/query", "serving/admission", "serving/replay",
                "dp/finalize", "dp/replay_slab_0",
                "dp/slab_transfer"} <= names
        # One root: the query; everything else parents into it.
        roots = [e for e in events if e["ph"] == "X"
                 and "parent_id" not in e["args"]]
        assert [e["name"] for e in roots] == ["serving/query"]
        # The in-memory span objects satisfy the schema invariants.
        validate_trace_schema(tracer.spans())

        snap = metrics_lib.query_seconds().snapshot(outcome="released")
        assert snap["count"] == before + 1
        assert snap["sum"] > 0
        prom = metrics_lib.default_registry().to_prometheus()
        assert "pipelinedp_tpu_query_seconds_bucket" in prom
        assert "pipelinedp_tpu_replay_seconds_bucket" in prom
        assert "pipelinedp_tpu_finalize_seconds_bucket" in prom

    def test_trace_disabled_trace_path_is_noop(self, tmp_path):
        trace_lib.shutdown()
        data = _data()
        trace_file = str(tmp_path / "none.json")
        with serving.DatasetSession(data, n_chunks=2,
                                    name="notrace") as session:
            _query_cols(session, trace_path=trace_file)
        assert not (tmp_path / "none.json").exists()


class TestAuditOutcomes:
    """Every typed outcome lands in the audit trail with the mechanism
    metadata and exact charge semantics."""

    def _session(self, **kw):
        return serving.DatasetSession(_data(), n_chunks=4, **kw)

    def test_released_record_carries_dp_output_counts(self):
        with self._session(name="aud-rel") as session:
            cols = _query_cols(session, seed=1)
            (rec,) = session.audit_trail.records()
        keep = np.asarray(cols["keep_mask"])
        assert rec.outcome == "released"
        assert rec.mechanisms == ("COUNT", "SUM")
        assert rec.noise_kind == "laplace"
        assert rec.epsilon == pytest.approx(1.0)
        assert rec.partitions_kept == int(keep.sum())
        assert rec.partitions_dropped == int(keep.size) - rec.partitions_kept
        assert rec.duration_s > 0
        assert rec.seed == 1

    def test_double_release_refused_recorded(self):
        with self._session(name="aud-dbl") as session:
            session.register_tenant("acme", total_epsilon=10.0, total_delta=1e-3)
            _query_cols(session, seed=2, tenant="acme")
            with pytest.raises(runtime.DoubleReleaseError):
                _query_cols(session, seed=2, tenant="acme")
            outcomes = [r.outcome for r in session.audit_trail.records()]
            assert outcomes == ["released", "double-release-refused"]
            recs = session.audit_trail.records()
            assert recs[0].token == recs[1].token
            # The refused query drew nothing: exactly one charge stands.
            assert session.tenant("acme").ledger.spent_epsilon == \
                pytest.approx(1.0)

    def test_shed_recorded(self):
        with self._session(name="aud-shed") as session:
            manager = serving.SessionManager(max_inflight=1)
            manager.attach(session)
            release = threading.Event()
            entered = threading.Event()

            def hog():
                with manager.admission():
                    entered.set()
                    release.wait(30)

            t = threading.Thread(target=hog)
            t.start()
            try:
                assert entered.wait(30)
                with pytest.raises(serving.SessionOverloadedError):
                    _query_cols(session, seed=3)
            finally:
                release.set()
                t.join()
            manager.remove(session.name)
            assert [r.outcome for r in session.audit_trail.records()] == \
                ["shed"]

    def test_deadline_expired_recorded(self):
        with self._session(name="aud-dl") as session:
            injector = runtime.FaultInjector(
                [runtime.FaultSpec("hang", at_slab=0, hang_s=15.0)])
            with pytest.raises(serving.QueryDeadlineError):
                _query_cols(session, seed=4, deadline_s=1.0,
                            fault_injector=injector)
            (rec,) = session.audit_trail.records()
            assert rec.outcome == "deadline-expired"

    def test_failed_query_recorded_as_refunded(self):
        with self._session(name="aud-ref") as session:
            session.register_tenant("acme", total_epsilon=10.0, total_delta=1e-3)
            injector = runtime.FaultInjector(
                [runtime.FaultSpec("host_crash", at_slab=0)])
            with pytest.raises(Exception):
                _query_cols(session, seed=5, tenant="acme",
                            fault_injector=injector)
            (rec,) = session.audit_trail.records()
            assert rec.outcome == "refunded"
            assert rec.tenant == "acme"
            # The charge was exactly refunded.
            assert session.tenant("acme").ledger.spent_epsilon == 0.0

    def test_query_batch_records_per_config(self):
        with self._session(name="aud-batch") as session:
            configs = [
                serving.QueryConfig(
                    metrics=[pdp.Metrics.COUNT], epsilon=1.0, delta=1e-6,
                    max_partitions_contributed=8,
                    max_contributions_per_partition=4, seed=100 + i)
                for i in range(3)
            ]
            session.query_batch(configs, secure_host_noise=False)
            recs = session.audit_trail.records()
            assert [r.outcome for r in recs] == ["released"] * 3
            assert sorted(r.seed for r in recs) == [100, 101, 102]
            assert all(r.partitions_kept >= 0 for r in recs)

    def test_trace_id_correlates_audit_span_and_capture(
            self, tracer, tmp_path, monkeypatch):
        """The PR-13 correlation satellite: one query's audit record,
        root span, flight events and slow-query capture all carry the
        same trace id."""
        cap_dir = str(tmp_path / "cap")
        monkeypatch.setenv(flight_lib.CAPTURE_DIR_ENV, cap_dir)
        monkeypatch.setenv(flight_lib.SLOW_QUERY_ENV, "0.000001")
        with self._session(name="aud-corr") as session:
            mark = flight_lib.recorder().watermark()
            _query_cols(session, seed=11)
            (rec,) = session.audit_trail.records()
        qid = rec.trace_id
        assert qid.startswith("q")
        root = next(s for s in tracer.spans()
                    if s.name == "serving/query")
        assert root.attrs["qid"] == qid
        kinds = {e.kind: e for e in
                 flight_lib.recorder().events(since_seq=mark)}
        assert kinds["query_start"].attrs["qid"] == qid
        assert kinds["query_finish"].attrs["qid"] == qid
        capture_path = os.path.join(cap_dir, f"slowquery_{qid}.json")
        assert os.path.exists(capture_path)
        capture = json.load(open(capture_path))
        assert capture["trace_id"] == qid
        assert capture["outcome"] == "released"
        assert capture["metrics_delta"].get(
            "serving/bound_cache_misses") == 1
        assert "query_start" in [e["kind"]
                                 for e in capture["flight_events"]]
        # Tracing was on: the capture embeds this query's Chrome trace.
        names = {e["name"] for e in capture["chrome_trace"]["traceEvents"]}
        assert "serving/query" in names

    def test_audit_durable_on_saved_session(self, tmp_path):
        store = serving.SessionStore(str(tmp_path))
        with self._session(name="aud-store") as session:
            _query_cols(session, seed=6)  # in-memory record pre-save
            session.save(store)
            assert session.audit_trail.durable
            _query_cols(session, seed=7)
        reopened = store.open("aud-store")
        try:
            assert [r.seed for r in reopened.audit_trail.records()] == \
                [6, 7]
            assert [r.outcome for r in reopened.audit_trail.records()] == \
                ["released", "released"]
        finally:
            reopened.close()


class TestOpsEndpointsLive:
    """The CI endpoint smoke (ISSUE 13): /metrics + /healthz + /statusz
    against a LIVE SessionManager serving real queries."""

    def test_endpoints_against_live_manager(self, tmp_path):
        data = _data()
        manager = serving.SessionManager(
            serving.SessionStore(str(tmp_path / "store")), ops_port=0)
        try:
            session = manager.create("live", data, n_chunks=4)
            session.register_tenant("acme", total_epsilon=10.0,
                                    total_delta=1e-3)
            _query_cols(session, seed=0, tenant="acme")
            _query_cols(session, seed=1)
            url = manager.ops_server.url

            prom = urllib.request.urlopen(url + "/metrics",
                                          timeout=10).read().decode()
            assert "pipelinedp_tpu_query_seconds_bucket" in prom
            assert "pipelinedp_tpu_events_total" in prom

            health = json.loads(urllib.request.urlopen(
                url + "/healthz", timeout=10).read())
            assert health["status"] == "ok"
            assert health["checks"]["sessions_resident"] == 1
            assert health["checks"]["wal_writable"] is True

            status = json.loads(urllib.request.urlopen(
                url + "/statusz", timeout=10).read())
            assert status["kind"] == "manager"
            live = status["sessions"]["live"]
            assert live["residency"] in ("device", "host")
            assert live["queries"] == 2
            acme = live["tenants"]["acme"]
            assert acme["spent_epsilon"] == pytest.approx(1.0)
            assert acme["epsilon_burn_pct"] == pytest.approx(10.0)
            assert status["counters"]["queries"] >= 2

            flightz = json.loads(urllib.request.urlopen(
                url + "/debug/flightz", timeout=10).read())
            assert "query_finish" in [e["kind"]
                                      for e in flightz["events"]]
        finally:
            manager.close()


class TestNoPrivateLeakScan:
    """Runs the serving matrix (success, batch, shed, deadline, refusal)
    with tracing on, then scans EVERY emitted obs record: span attrs,
    span events, metric label values, audit fields — and (PR 13) every
    operational-plane surface: the /statusz, /healthz and
    /debug/flightz payloads, the flight-recorder dump, and the
    slow-query capture bundles. Nothing may be array-shaped, carry a
    forbidden key, or contain a pid/pk sentinel."""

    def _scan_value(self, key, value, where):
        assert key not in metrics_lib.FORBIDDEN_KEYS, \
            f"forbidden key {key!r} in {where}"
        assert value is None or isinstance(
            value, (bool, int, float, str)), \
            f"non-scalar {type(value).__name__} under {key!r} in {where}"
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            assert not (PID_LO <= value < PID_HI), \
                f"pid sentinel {value} leaked via {key!r} in {where}"
        if isinstance(value, str):
            for sentinel in (str(PID_LO), str(PID_LO + 1)):
                assert sentinel not in value, \
                    f"pid sentinel inside string {key!r} in {where}"

    def _scan_json(self, node, where, key="root"):
        """Recursive scan of an operational-plane JSON payload: every
        dict key is checked against the forbidden set, every leaf
        against the sentinel window. One carve-out: the Chrome
        trace-event schema requires a literal ``pid`` key — it must
        hold the OS process id, never anything else."""
        if isinstance(node, dict):
            for k, v in node.items():
                if k == "pid":
                    assert v == os.getpid(), \
                        f"chrome 'pid' is not the process id in {where}"
                    continue
                assert k not in metrics_lib.FORBIDDEN_KEYS, \
                    f"forbidden key {k!r} in {where}"
                self._scan_json(v, where, key=k)
        elif isinstance(node, (list, tuple)):
            for item in node:
                self._scan_json(item, where, key=key)
        else:
            self._scan_value(key if key not in ("root",) else "leaf",
                             node, where)

    def test_full_matrix_emits_no_private_data(self, tracer, tmp_path,
                                               monkeypatch):
        cap_dir = str(tmp_path / "cap")
        monkeypatch.setenv(flight_lib.CAPTURE_DIR_ENV, cap_dir)
        monkeypatch.setenv(flight_lib.SLOW_QUERY_ENV, "0.000001")
        registry = metrics_lib.default_registry()
        data = _data()
        with serving.DatasetSession(data, n_chunks=4,
                                    name="leakscan") as session:
            session.register_tenant("acme", total_epsilon=50.0, total_delta=1e-3)
            _query_cols(session, seed=0, tenant="acme")
            _query_cols(session, seed=0)  # bound-cache hit
            session.query_batch([
                serving.QueryConfig(
                    metrics=[pdp.Metrics.COUNT], epsilon=1.0,
                    delta=1e-6, max_partitions_contributed=8,
                    max_contributions_per_partition=4, seed=50)
            ], secure_host_noise=False)
            with pytest.raises(runtime.DoubleReleaseError):
                _query_cols(session, seed=0, tenant="acme")
            injector = runtime.FaultInjector(
                [runtime.FaultSpec("hang", at_slab=0, hang_s=10.0)])
            with pytest.raises(serving.QueryDeadlineError):
                _query_cols(session, seed=9, deadline_s=0.8,
                            fault_injector=injector)

            # -- scan spans (attrs + events) -----------------------------
            spans = tracer.spans()
            assert spans, "matrix produced no spans"
            for span in spans:
                for k, v in span.attrs.items():
                    self._scan_value(k, v, f"span {span.name}")
                for ev_name, _, ev_attrs in span.events:
                    for k, v in ev_attrs.items():
                        self._scan_value(k, v,
                                         f"event {ev_name} in {span.name}")

            # -- scan the metric families (names, labels) ----------------
            snap = registry.snapshot()
            for fam_name, fam in snap["families"].items():
                for label_str in fam["series"]:
                    for pair in filter(None, label_str.split(",")):
                        k, _, v = pair.partition("=")
                        self._scan_value(k, v, f"metric {fam_name}")

            # -- scan every audit field ----------------------------------
            for rec in session.audit_trail.records():
                for k, v in rec.to_payload().items():
                    if k == "mechanisms":
                        assert all(isinstance(m, str) for m in v)
                        continue
                    self._scan_value(k, v, f"audit record {rec.seq}")

            # -- scan the operational plane (PR 13 satellite): the live
            # endpoints, a flight-recorder dump, and every slow-query
            # capture the matrix produced -------------------------------
            with serving.serve_ops(session, port=0) as srv:
                for endpoint in ("/statusz", "/healthz",
                                 "/debug/flightz"):
                    body = urllib.request.urlopen(
                        srv.url + endpoint, timeout=10).read()
                    self._scan_json(json.loads(body),
                                    f"endpoint {endpoint}")
            dump_path = flight_lib.recorder().dump(
                str(tmp_path / "flight.json"), reason="leak-scan")
            self._scan_json(flight_lib.read_dump(dump_path),
                            "flight dump")
            captures = os.listdir(cap_dir)
            assert captures, "the matrix produced no slow-query capture"
            for name in captures:
                with open(os.path.join(cap_dir, name)) as f:
                    self._scan_json(json.load(f), f"capture {name}")
