"""The trace reduction on traces with known answers, and on a small trace
recorded on a TPU v5e."""

import os

import pytest

from benchmark import trace_reduce
from benchmark.metrics import _chunk_programs

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def xspace(device_planes, host_events):
    """Text-proto XSpace: device planes {plane: {line: [(name, s, e)]}}
    and host events [(name, s, e)], times in ns."""
    out = []
    pid = 0
    for plane, lines in list(device_planes.items()) + [
            ("/host:CPU", {"python": host_events})]:
        pid += 1
        names = {}
        body = []
        for lid, (line, events) in enumerate(lines.items(), 1):
            evs = []
            for name, s, e in events:
                mid = names.setdefault(name, len(names) + 1)
                evs.append(f"events {{ metadata_id: {mid} "
                           f"offset_ps: {s * 1000} "
                           f"duration_ps: {(e - s) * 1000} }}")
            body.append(f'lines {{ id: {lid} name: "{line}" '
                        f'timestamp_ns: 0 {" ".join(evs)} }}')
        meta = [f'event_metadata {{ key: {i} value {{ id: {i} '
                f'name: "{n}" }} }}' for n, i in names.items()]
        out.append(f'planes {{ id: {pid} name: "{plane}" '
                   f'{" ".join(body + meta)} }}')
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto("\n".join(out))


# One chip, a window [500, 10500) ns:
#   ops busy [1000, 4000) (two overlapping ops), [5000, 6000),
#   [8000, 9000), [9500, 9600), and one op after the window;
#   idle [500, 1000) dp/encode, [4000, 5000) dp/wire_sort (nested in
#   dp/stream_slab_0), [6000, 8000) + [9000, 9500) + [9600, 10500)
#   dp/finalize.
ONE_CHIP = {"/device:TPU:0": {
    "XLA Modules": [("jit__chunk_step_rle_compact(11)", 1000, 6000),
                    ("jit_merge_compact_chunks(12)", 8000, 9000),
                    ("jit_greater(13)", 9500, 9600),
                    ("jit__chunk_step_rle_compact(11)", 11000, 12000)],
    "XLA Ops": [("fusion.1", 1000, 3000), ("sort.2", 2000, 4000),
                ("fusion.3", 5000, 6000), ("scatter.4", 8000, 9000),
                ("compare.5", 9500, 9600), ("fusion.1", 11000, 12000)],
}}
HOST = [("bench/window", 500, 10500), ("bench/aggregate", 500, 10500),
        ("dp/encode", 500, 1000), ("dp/stream_slab_0", 3000, 7000),
        ("dp/wire_sort", 3900, 4800), ("dp/finalize", 6500, 10500),
        ("not/annotated", 0, 20000)]


def test_busy_union_and_idle_share():
    r = trace_reduce.reduce_profile(xspace(ONE_CHIP, HOST))
    assert r.window_s == pytest.approx(10000e-9)
    assert r.busy_s == pytest.approx(5100e-9)
    assert r.idle_share == pytest.approx(0.49)
    assert r.n_devices == 1


def test_programs_are_named_without_fingerprint_and_clipped_to_window():
    r = trace_reduce.reduce_profile(xspace(ONE_CHIP, HOST))
    assert r.program_s == pytest.approx({
        "_chunk_step_rle_compact": 5000e-9,
        "merge_compact_chunks": 1000e-9, "greater": 100e-9})
    assert r.programs_matching(_chunk_programs.PATTERNS) == pytest.approx(
        6000e-9)


def test_gaps_take_the_innermost_open_span():
    r = trace_reduce.reduce_profile(xspace(ONE_CHIP, HOST))
    assert r.idle_by_span == pytest.approx({
        "dp/encode": 500e-9, "dp/wire_sort": 1000e-9,
        "dp/finalize": 3400e-9})
    out = r.breakdown()
    assert out["device_ops"][0] == ["_chunk_step_rle_compact",
                                    pytest.approx(5000e-9)]
    assert [k for k, _ in out["idle_gaps"]] == [
        "dp/finalize", "dp/wire_sort", "dp/encode"]


def test_gap_outside_every_span_is_labelled():
    r = trace_reduce.reduce_profile(xspace(
        ONE_CHIP, [("bench/window", 0, 1000)]))
    assert r.idle_by_span == {trace_reduce.NO_SPAN: pytest.approx(1000e-9)}


def test_busy_is_averaged_over_chips():
    two = dict(ONE_CHIP)
    two["/device:TPU:1"] = {"XLA Ops": [("fusion.1", 1000, 2000)]}
    r = trace_reduce.reduce_profile(xspace(two, HOST))
    assert r.n_devices == 2
    assert r.busy_s == pytest.approx((5100 + 1000) / 2 * 1e-9)


def test_a_trace_without_window_or_chip_is_refused():
    with pytest.raises(ValueError, match="bench/window"):
        trace_reduce.reduce_profile(xspace(ONE_CHIP, HOST[1:]))
    with pytest.raises(ValueError, match="TPU"):
        trace_reduce.reduce_profile(xspace({}, HOST))


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: a sort program named like a chunk
    step and a merge, inside the window and dp/ spans."""
    path = os.path.join(FIXTURES, "tpu_small.xplane.pb")
    r = trace_reduce.reduce(path)
    assert r.n_devices == 1
    assert 0 < r.busy_s < r.window_s
    assert r.program_s["_chunk_step_demo"] > 0
    assert r.program_s["merge_compact_chunks"] > 0
    assert r.programs_matching(_chunk_programs.PATTERNS) == pytest.approx(
        r.program_s["_chunk_step_demo"] + r.program_s["merge_compact_chunks"])
    assert "dp/encode" in r.idle_by_span
