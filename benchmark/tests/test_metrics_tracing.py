"""The readers of the slab driver's spans and wire counters, on synthetic
stage dicts and counters."""

import pytest

from benchmark import common, run


def ctx(items=()):
    w = common.Window(items=list(items), seconds=1.0)
    return run.Context(config={}, window=w, setup_s=0.0, trace=None,
                       peaks={})


def item(ok=True, stages=None):
    return common.Item(work=1, ok=ok, stages=stages or {})


@pytest.mark.parametrize("metric,stage", [
    ("h2d_transfer_s", "dp/slab_transfer"),
    ("slab_wait_s", "dp/slab_wait"),
])
def test_span_readers_average_completed_aggregates(metric, stage):
    reader = run.load_reader(metric)
    c = ctx([item(stages={stage: 0.5, "dp/stream_slab_0": 9.0}),
             item(stages={stage: 1.5}),
             item(ok=False, stages={stage: 100.0})])
    assert reader.read(c) == pytest.approx(1.0)
    # A program without the span (an older parent) reads nothing.
    assert reader.read(ctx([item(stages={"dp/stream_slab_0": 9.0})])) \
        is None


def test_slab_wait_is_zero_for_a_transfer_without_a_window():
    reader = run.load_reader("slab_wait_s")
    c = ctx([item(stages={"dp/slab_transfer": 0.2}),
             item(stages={"dp/slab_transfer": 0.2, "dp/slab_wait": 1.0})])
    assert reader.read(c) == pytest.approx(0.5)


def test_wire_bytes_per_row_reads_the_process_counters(monkeypatch):
    from pipelinedp_tpu import profiler
    reader = run.load_reader("wire_bytes_per_row")
    counts = {"ops/wire_bytes": 3_000, "ops/wire_rows": 1_200}
    monkeypatch.setattr(profiler, "event_count",
                        lambda name: counts.get(name, 0))
    assert reader.read(ctx()) == pytest.approx(2.5)
    counts.clear()
    assert reader.read(ctx()) is None
