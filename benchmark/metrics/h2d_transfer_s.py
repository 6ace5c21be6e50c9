"""Host seconds of the host-to-device transfer per aggregate: the
``dp/slab_transfer`` spans (runtime/driver.py, the host side of
``DevicePlacement.transfer``; the single-shot path below 2M rows ships
its columns under the same span), summed over an aggregate's slab
windows. A program without those spans reads nothing."""

from benchmark.metrics import _stages


def read(ctx):
    return _stages.mean_over_items(
        ctx, lambda st: st.get("dp/slab_transfer"))
