"""Unit tests for pipelinedp_tpu/obs/: tracer, metrics registry, audit.

Covers the PR-11 acceptance surface that doesn't need an engine:
trace-schema validation (parents resolve, spans nest within parents),
histogram bucket correctness, Prometheus exposition shape, audit-WAL
torn-tail recovery, the profiler back-compat shims, and the
reset-vs-increment atomicity hammer (the counter-hygiene satellite).
"""

import json
import math
import re
import threading

import pytest

from pipelinedp_tpu import profiler
from pipelinedp_tpu.obs import audit as audit_lib
from pipelinedp_tpu.obs import metrics as metrics_lib
from pipelinedp_tpu.obs import trace as trace_lib


@pytest.fixture
def tracer():
    t = trace_lib.install(trace_lib.Tracer())
    try:
        yield t
    finally:
        trace_lib.shutdown()


def make_registry():
    return metrics_lib.MetricsRegistry()


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def validate_trace_schema(spans):
    """The PR-11 trace invariants: every span has a parent except roots,
    parents resolve within the trace, children nest inside their
    parent's [t0, t0+dur] window, and ids are unique."""
    by_id = {s.span_id: s for s in spans}
    assert len(by_id) == len(spans), "duplicate span ids"
    for s in spans:
        assert s.dur_ns >= 0, f"unfinished span {s.name} exported"
        if s.parent_id is None:
            assert s.trace_id == s.span_id
            continue
        parent = by_id.get(s.parent_id)
        assert parent is not None, \
            f"span {s.name} has dangling parent {s.parent_id}"
        assert s.trace_id == parent.trace_id
        assert s.t0_ns >= parent.t0_ns
        assert s.t0_ns + s.dur_ns <= parent.t0_ns + parent.dur_ns, \
            f"span {s.name} escapes parent {parent.name}"


class TestTracer:

    def test_nesting_and_parent_links(self, tracer):
        with tracer.span("root") as root:
            with tracer.span("child") as child:
                with tracer.span("grandchild") as gc:
                    pass
        spans = tracer.spans()
        assert [s.name for s in spans] == ["grandchild", "child", "root"]
        assert child.parent_id == root.span_id
        assert gc.parent_id == child.span_id
        assert root.parent_id is None
        assert {s.trace_id for s in spans} == {root.span_id}
        validate_trace_schema(spans)

    def test_sibling_roots_get_distinct_traces(self, tracer):
        with tracer.span("a"):
            pass
        with tracer.span("b"):
            pass
        a, b = tracer.spans()
        assert a.trace_id != b.trace_id
        validate_trace_schema(tracer.spans())

    def test_events_attach_to_current_span(self, tracer):
        with tracer.span("work") as span:
            tracer.event("retry", attempt=1)
        assert [e[0] for e in span.events] == ["retry"]
        assert span.events[0][2] == {"attempt": 1}
        # No open span: dropped, never raises.
        tracer.event("orphan")

    def test_cross_thread_attach(self, tracer):
        with tracer.span("root") as root:
            def worker():
                with tracer.attach(root):
                    with tracer.span("worker-span"):
                        pass
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        worker_span = next(s for s in tracer.spans()
                           if s.name == "worker-span")
        assert worker_span.parent_id == root.span_id
        validate_trace_schema(tracer.spans())

    def test_error_span_marked(self, tracer):
        with pytest.raises(ValueError):
            with tracer.span("failing"):
                raise ValueError("boom")
        (span,) = tracer.spans()
        assert span.attrs["error"] is True

    def test_disabled_is_shared_null_context(self):
        # No tracer: a span records no Span, but it still annotates the
        # profiler trace and feeds the stage sinks.
        trace_lib.shutdown()
        with trace_lib.collect_stage_times() as stages:
            with trace_lib.span("x", a=1) as span:
                assert span is None
                assert trace_lib.current() is None
            with trace_lib.span("y") as span:
                assert span is None
        assert set(stages) == {"x", "y"}
        trace_lib.event("nothing")  # no-op, no error
        assert trace_lib.current() is None

    def test_chrome_export_schema(self, tracer, tmp_path):
        with tracer.span("root", knob=3):
            with tracer.span("child"):
                tracer.event("mark", detail="x")
        doc = tracer.export_chrome()
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        complete = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        assert {e["name"] for e in complete} == {"root", "child"}
        assert [e["name"] for e in instants] == ["mark"]
        for e in complete:
            assert {"pid", "tid", "ts", "dur", "args"} <= set(e)
            assert "span_id" in e["args"]
        root_ev = next(e for e in complete if e["name"] == "root")
        assert root_ev["args"]["knob"] == 3
        assert "parent_id" not in root_ev["args"]
        # File form round-trips as JSON (Perfetto-loadable).
        path = tracer.write_chrome(str(tmp_path / "t.json"))
        assert json.load(open(path)) == json.loads(json.dumps(doc))

    def test_per_trace_export_filter(self, tracer):
        with tracer.span("query-1") as q1:
            with tracer.span("inner"):
                pass
        with tracer.span("query-2"):
            pass
        events = tracer.export_chrome(trace_id=q1.trace_id)["traceEvents"]
        assert {e["name"] for e in events} == {"query-1", "inner"}

    def test_forbidden_attr_keys_refused(self, tracer):
        with pytest.raises(metrics_lib.TelemetryLeakError):
            tracer.span("bad", pid=123)
        with tracer.span("ok") as span:
            with pytest.raises(metrics_lib.TelemetryLeakError):
                span.set_attribute("partition_key", "k")
            with pytest.raises(metrics_lib.TelemetryLeakError):
                span.add_event("ev", value=1.0)

    def test_non_scalar_attr_refused(self, tracer):
        with pytest.raises(metrics_lib.TelemetryLeakError):
            tracer.span("bad", rows=[1, 2, 3])

    def test_bounded_span_buffer(self):
        t = trace_lib.Tracer(max_spans=3)
        for i in range(5):
            with t.span(f"s{i}"):
                pass
        assert [s.name for s in t.spans()] == ["s2", "s3", "s4"]


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


class TestMetricsRegistry:

    def test_counter_and_labels(self):
        reg = make_registry()
        c = reg.counter("pdp_test_queries", "help text")
        c.inc()
        c.inc(2, outcome="released")
        c.inc(outcome="released")
        assert c.value() == 1
        assert c.value(outcome="released") == 3
        # Same name returns the same family; a different type conflicts.
        assert reg.counter("pdp_test_queries") is c
        with pytest.raises(ValueError):
            reg.gauge("pdp_test_queries")

    def test_gauge(self):
        reg = make_registry()
        g = reg.gauge("pdp_test_bytes")
        g.set(100)
        g.inc(5)
        g.dec(3)
        assert g.value() == 102

    def test_histogram_bucket_correctness(self):
        reg = make_registry()
        h = reg.histogram("pdp_test_lat", buckets=(0.1, 1.0, 10.0))
        # Boundary semantics are Prometheus `le` (inclusive upper).
        for v in (0.05, 0.1, 0.10001, 1.0, 5.0, 10.0, 99.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["buckets"] == [0.1, 1.0, 10.0, math.inf]
        # cumulative: le 0.1 -> {0.05, 0.1}; le 1 -> +{0.10001, 1.0};
        # le 10 -> +{5.0, 10.0}; +Inf -> +{99.0}
        assert snap["counts"] == [2, 4, 6, 7]
        assert snap["count"] == 7
        assert snap["sum"] == pytest.approx(sum(
            (0.05, 0.1, 0.10001, 1.0, 5.0, 10.0, 99.0)))

    def test_histogram_labels_and_default_buckets(self):
        reg = make_registry()
        h = reg.histogram("pdp_test_q")
        h.observe(0.02, outcome="released")
        h.observe(3.0, outcome="shed")
        assert h.snapshot(outcome="released")["count"] == 1
        assert (len(h.snapshot(outcome="released")["buckets"])
                == len(metrics_lib.DEFAULT_LATENCY_BUCKETS_S) + 1)

    def test_prometheus_exposition_schema(self):
        reg = make_registry()
        reg.counter("pdp_c", "a counter").inc(2, kind="x")
        reg.gauge("pdp_g").set(7)
        h = reg.histogram("pdp_h", buckets=(1.0, 2.0))
        h.observe(1.5)
        reg.event_inc("serving/queries", 3)
        text = reg.to_prometheus()
        lines = text.splitlines()
        assert "# TYPE pdp_c_total counter" in lines
        assert 'pdp_c_total{kind="x"} 2' in lines
        assert "# TYPE pdp_g gauge" in lines
        assert "pdp_g 7" in lines
        assert "# TYPE pdp_h histogram" in lines
        assert 'pdp_h_bucket{le="1"} 0' in lines
        assert 'pdp_h_bucket{le="2"} 1' in lines
        assert 'pdp_h_bucket{le="+Inf"} 1' in lines
        assert "pdp_h_sum 1.5" in lines
        assert "pdp_h_count 1" in lines
        assert ('pipelinedp_tpu_events_total{event="serving/queries"} 3'
                in lines)
        # Every sample line is format-0.0.4 parseable.
        sample_re = re.compile(
            r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? \S+$')
        for line in lines:
            if line and not line.startswith("#"):
                assert sample_re.match(line), line

    def test_snapshot_is_json_able(self):
        reg = make_registry()
        reg.counter("pdp_c").inc()
        reg.histogram("pdp_h", buckets=(1.0,)).observe(0.5)
        reg.event_inc("runtime/retries")
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["events"] == {"runtime/retries": 1}
        assert snap["families"]["pdp_c"]["kind"] == "counter"
        assert snap["families"]["pdp_h"]["kind"] == "histogram"

    def test_forbidden_label_refused(self):
        reg = make_registry()
        with pytest.raises(metrics_lib.TelemetryLeakError):
            reg.counter("pdp_c").inc(pid="u1")
        with pytest.raises(metrics_lib.TelemetryLeakError):
            reg.histogram("pdp_h").observe(0.1, partition_key="k")

    def test_event_namespace_reset_prefix(self):
        reg = make_registry()
        reg.event_inc("a/x", 2)
        reg.event_inc("b/y", 3)
        reg.reset_events("a/")
        assert reg.event_values() == {"b/y": 3}
        reg.reset_events()
        assert reg.event_values() == {}


class TestProfilerShims:
    """profiler.count_event/event_count/event_counts/reset_events are
    back-compat views over the default registry's event namespace."""

    def test_count_event_lands_in_registry(self):
        profiler.reset_events("obs_shim_test/")
        profiler.count_event("obs_shim_test/hits", 4)
        assert profiler.event_count("obs_shim_test/hits") == 4
        assert metrics_lib.default_registry().event_value(
            "obs_shim_test/hits") == 4
        assert profiler.event_counts()["obs_shim_test/hits"] == 4
        profiler.reset_events("obs_shim_test/")
        assert profiler.event_count("obs_shim_test/hits") == 0

    def test_reset_vs_increment_hammer(self):
        """The counter-hygiene satellite: reset_events(prefix) racing
        count_event from many threads must be atomic — an unrelated
        prefix NEVER loses increments, and the hammered prefix never
        errors or goes negative."""
        n_threads, n_incs = 8, 2000
        profiler.reset_events("hammer/")
        profiler.reset_events("stable/")
        stop = threading.Event()

        def incrementer():
            for _ in range(n_incs):
                profiler.count_event("stable/total")
                profiler.count_event("hammer/racy")

        def resetter():
            while not stop.is_set():
                profiler.reset_events("hammer/")

        threads = [threading.Thread(target=incrementer)
                   for _ in range(n_threads)]
        killer = threading.Thread(target=resetter)
        killer.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        killer.join()
        # The unrelated prefix kept every single increment.
        assert profiler.event_count("stable/total") == n_threads * n_incs
        # The hammered counter is consistent (>= 0; exact value depends
        # on the last reset's timing).
        assert profiler.event_count("hammer/racy") >= 0
        profiler.reset_events("hammer/")
        profiler.reset_events("stable/")

    def test_snapshot_while_incrementing_never_errors(self):
        done = threading.Event()

        def writer():
            i = 0
            while not done.is_set():
                profiler.count_event(f"snaphammer/{i % 50}")
                i += 1

        t = threading.Thread(target=writer)
        t.start()
        try:
            reg = metrics_lib.default_registry()
            for _ in range(300):
                json.dumps(reg.snapshot())
                reg.to_prometheus()
        finally:
            done.set()
            t.join()
        profiler.reset_events("snaphammer/")


# ---------------------------------------------------------------------------
# Audit trail
# ---------------------------------------------------------------------------


def _record(trail, seed=0, outcome="released", tenant="acme",
            trace_id=""):
    return trail.record(
        session="s", tenant=tenant, token=f"('fp', {seed})",
        outcome=outcome, mechanisms=["COUNT", "SUM"],
        noise_kind="laplace", epsilon=1.0, delta=1e-6,
        partitions_kept=10, partitions_dropped=5, duration_s=0.25,
        seed=seed, trace_id=trace_id)


class TestAuditTrail:

    def test_record_fields_and_tenant_filter(self):
        trail = audit_lib.AuditTrail()
        _record(trail, seed=0, tenant="acme")
        _record(trail, seed=1, tenant="bob", outcome="refunded")
        assert len(trail) == 2
        acme = trail.records(tenant="acme")
        assert len(acme) == 1
        r = acme[0]
        assert (r.seq, r.outcome, r.mechanisms) == (
            0, "released", ("COUNT", "SUM"))
        assert r.partitions_kept == 10 and r.partitions_dropped == 5
        assert trail.records()[1].outcome == "refunded"

    def test_unknown_outcome_refused(self):
        trail = audit_lib.AuditTrail()
        with pytest.raises(ValueError, match="outcome"):
            _record(trail, outcome="maybe")

    def test_durable_roundtrip(self, tmp_path):
        path = str(tmp_path / "audit.wal")
        trail = audit_lib.AuditTrail(path)
        _record(trail, seed=0)
        _record(trail, seed=1, outcome="shed")
        trail.close()
        reopened = audit_lib.AuditTrail(path)
        assert [r.to_payload() for r in reopened.records()] == \
            [r.to_payload() for r in trail.records()]
        # Appends continue the sequence.
        _record(reopened, seed=2, outcome="deadline-expired")
        assert [r.seq for r in reopened.records()] == [0, 1, 2]

    def test_torn_tail_recovery(self, tmp_path):
        path = str(tmp_path / "audit.wal")
        trail = audit_lib.AuditTrail(path)
        _record(trail, seed=0)
        _record(trail, seed=1)
        trail.close()
        with open(path, "ab") as f:
            f.write(b'{"seq": 2, "torn mid-append')
        reopened = audit_lib.AuditTrail(path)
        assert len(reopened) == 2  # the torn record was never acked
        _record(reopened, seed=2)
        reopened.close()
        # The truncated tail never fuses with the new append.
        final = audit_lib.AuditTrail(path)
        assert [r.seed for r in final.records()] == [0, 1, 2]

    def test_interior_corruption_refused(self, tmp_path):
        path = str(tmp_path / "audit.wal")
        trail = audit_lib.AuditTrail(path)
        _record(trail, seed=0)
        _record(trail, seed=1)
        trail.close()
        lines = open(path, "rb").read().splitlines(keepends=True)
        with open(path, "wb") as f:
            f.write(b'{"seq": 0, "garbage": true}\n')
            f.writelines(lines[1:])
        with pytest.raises(audit_lib.AuditCorruptError):
            audit_lib.AuditTrail(path)

    def test_bind_migrates_in_memory_records(self, tmp_path):
        path = str(tmp_path / "audit.wal")
        trail = audit_lib.AuditTrail()
        _record(trail, seed=0)
        assert not trail.durable
        trail.bind(path)
        assert trail.durable
        _record(trail, seed=1)
        trail.close()
        reopened = audit_lib.AuditTrail(path)
        assert [r.seed for r in reopened.records()] == [0, 1]
        # bind on an already-durable trail is a no-op.
        reopened.bind(str(tmp_path / "other.wal"))
        assert reopened.path == path

    def test_bind_after_prior_process_appends_after_recovery(
            self, tmp_path):
        path = str(tmp_path / "audit.wal")
        first = audit_lib.AuditTrail(path)
        _record(first, seed=0)
        first.close()
        # A fresh in-memory trail (new process, queries before save).
        second = audit_lib.AuditTrail()
        _record(second, seed=1)
        second.bind(path)
        assert [r.seed for r in second.records()] == [0, 1]
        assert [r.seq for r in second.records()] == [0, 1]

    def test_records_survive_json_roundtrip(self):
        trail = audit_lib.AuditTrail()
        r = _record(trail)
        assert audit_lib.AuditRecord.from_payload(
            json.loads(json.dumps(r.to_payload()))) == r

    def test_trace_id_recorded_and_persisted(self, tmp_path):
        path = str(tmp_path / "audit.wal")
        trail = audit_lib.AuditTrail(path)
        _record(trail, trace_id="q123-7")
        trail.close()
        reopened = audit_lib.AuditTrail(path)
        assert reopened.records()[0].trace_id == "q123-7"

    def test_pr11_records_without_trace_id_still_read(self, tmp_path):
        """Back-compat pin (ISSUE 13): a WAL written before the
        trace_id field existed must recover cleanly, reading the
        missing field as the empty string — and appends after recovery
        (which do carry trace_id) coexist in one file."""
        from pipelinedp_tpu.runtime import journal as journal_lib

        path = str(tmp_path / "audit.wal")
        trail = audit_lib.AuditTrail(path)
        pr11_payload = _record(trail, seed=0).to_payload()
        trail.close()
        # Rewrite the WAL with the PR-11 schema (no trace_id key).
        del pr11_payload["trace_id"]
        wal = journal_lib.JsonlWal(path)
        wal.rewrite([pr11_payload])
        wal.close()
        reopened = audit_lib.AuditTrail(path)
        assert len(reopened) == 1
        assert reopened.records()[0].trace_id == ""
        assert reopened.records()[0].seed == 0
        _record(reopened, seed=1, trace_id="q9-1")
        reopened.close()
        final = audit_lib.AuditTrail(path)
        assert [r.trace_id for r in final.records()] == ["", "q9-1"]
