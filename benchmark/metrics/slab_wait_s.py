"""Main-thread seconds per aggregate until each slab window's host slab is
ready: the slab driver's ``dp/slab_wait`` spans (runtime/driver.py), a
wait on the prefetch thread or the inline encode of a window nothing
prefetched. An aggregate that shipped its columns without a slab window
(the single-shot path below 2M rows: a ``dp/slab_transfer`` span and no
``dp/slab_wait``) waited 0 s. A program without those spans reads
nothing."""

from benchmark.metrics import _stages


def _wait_s(stages: dict):
    if "dp/slab_wait" in stages:
        return stages["dp/slab_wait"]
    return 0.0 if "dp/slab_transfer" in stages else None


def read(ctx):
    return _stages.mean_over_items(ctx, _wait_s)
