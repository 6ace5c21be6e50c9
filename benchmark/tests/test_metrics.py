"""The metric readers' arithmetic, the roofline bytes and the peaks."""

import types

import pytest

from benchmark import common, run
from benchmark.metrics import _roofline_bytes, _stages


def ctx(items=(), seconds=1.0, trace=None, config=None, setup_s=0.0):
    w = common.Window(items=list(items), seconds=seconds)
    return run.Context(config=config or {}, window=w, setup_s=setup_s,
                       trace=trace, peaks={"hbm_bytes_per_s": 819e9})


def item(start=0.0, end=1.0, ok=True, work=1, stages=None):
    return common.Item(work=work, start=start, end=end, ok=ok,
                       stages=stages or {})


WIDE = {"data": {"n_rows": 100_000_000, "n_partitions": 1_000_000},
         "aggregate": {"metrics": ["COUNT", "SUM"]}}
NETFLIX = {"data": {"n_rows": 100_480_507, "n_partitions": 17_770},
           "aggregate": {"metrics": ["COUNT", "SUM", "PRIVACY_ID_COUNT"]}}


def test_bound_bytes_count_rows_once_and_accumulators_once():
    assert _roofline_bytes.bound_bytes(WIDE) == (
        100_000_000 * 12 + 1_000_000 * 4 * 3)
    assert _roofline_bytes.bound_bytes(NETFLIX) == (
        100_480_507 * 12 + 17_770 * 4 * 3)


def test_bound_roofline_and_device_seconds():
    trace = types.SimpleNamespace(
        programs_matching=lambda patterns: 70.0, idle_share=0.25)
    c = ctx([item(), item()], trace=trace, config=WIDE)
    assert run.load_reader("bound_device_s").read(c) == 35.0
    want = 100.0 * _roofline_bytes.bound_bytes(WIDE) / 819e9 / 35.0
    assert run.load_reader("bound_roofline").read(c) == pytest.approx(want)
    assert run.load_reader("device_idle_pct.batch").read(c) == 25.0


def test_device_readers_read_nothing_without_trace_or_programs():
    assert run.load_reader("bound_roofline").read(ctx([item()])) is None
    trace = types.SimpleNamespace(programs_matching=lambda patterns: 0.0)
    c = ctx([item()], trace=trace, config=WIDE)
    assert run.load_reader("bound_roofline").read(c) is None


def test_peaks_lookup_refuses_an_unknown_device():
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    cell = run.resolve(bench, "netflix.batch", "TPU v5 lite")
    assert cell.peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="no peaks"):
        run.resolve(bench, "netflix.batch", "TPU v9 imaginary")


def test_host_encode_folds_only_top_level_main_thread_stages():
    stages = {"dp/encode": 1.0, "dp/wire_prep": 2.0, "dp/wire_sort": 5.0,
              "dp/wire_sort_parallel": 7.0, "dp/stream_slab_0": 3.0,
              "dp/stream_slab_2": 4.0, "dp/finalize": 9.0}
    assert _stages.host_encode_s(stages) == 10.0
    c = ctx([item(stages=stages), item(stages={"dp/encode": 2.0}),
             item(ok=False, stages={"dp/encode": 100.0})])
    assert run.load_reader("host_encode_s").read(c) == 6.0


def test_epilogue_is_finalize_less_its_transfer():
    c = ctx([item(stages={"dp/finalize": 0.5, "dp/finalize_transfer": 0.2}),
             item(stages={"dp/finalize": 0.3})])
    assert run.load_reader("epilogue_ms.batch").read(c) == pytest.approx(
        300.0)


def test_rows_per_s_counts_completed_aggregates_over_the_window():
    c = ctx([item(work=100), item(work=100), item(work=100, ok=False)],
            seconds=4.0)
    assert run.load_reader("rows_per_s").read(c) == 50.0

