"""Stage-time folding shared by the readers of program spans.

``profiler.collect_stage_times`` sums host wall seconds per stage name,
nested stages included in their parents. The host encode layer's
main-thread time is its top-level stages: ``dp/encode``, ``dp/wire_prep``,
``dp/wire_sort_upfront``, ``dp/wire_encode`` and every
``dp/stream_slab_<n>`` window. A slab window holds more than encoding:
the pipelined ``dp/wire_sort``, the wait on the prefetch thread, the
host-to-device transfer of the slab and the dispatch of its chunk
programs, so the metric also moves with transfer and dispatch stalls.
``dp/wire_sort_parallel`` runs
on prefetch threads, overlapped with the main thread, and is left out.
The host epilogue is ``dp/finalize`` less its nested
``dp/finalize_transfer``, which waits for the device.
"""

ENCODE_STAGES = ("dp/encode", "dp/wire_prep", "dp/wire_sort_upfront",
                 "dp/wire_encode")
SLAB_PREFIX = "dp/stream_slab_"


def host_encode_s(stages: dict) -> float:
    return (sum(stages.get(k, 0.0) for k in ENCODE_STAGES)
            + sum(v for k, v in stages.items() if k.startswith(SLAB_PREFIX)))


def host_epilogue_s(stages: dict):
    if "dp/finalize" not in stages:
        return None
    return stages["dp/finalize"] - stages.get("dp/finalize_transfer", 0.0)


def mean_over_items(ctx, fn):
    """Mean of fn(stage times) over the window's completed items; None
    when no item has a reading."""
    vals = [fn(it.stages) for it in ctx.window.items if it.ok]
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(vals) if vals else None
