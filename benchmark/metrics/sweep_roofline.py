"""Share of the batched chunk program's device time that HBM bandwidth
alone would need: the sweep's least bytes per batch / peak bytes per
second / ``sweep_device_s``, in percent."""

from benchmark.metrics import _sweep_bytes, sweep_device_s


def read(ctx):
    t = sweep_device_s.read(ctx)
    if t is None:
        return None
    least = _sweep_bytes.least_bytes(ctx.config) / ctx.peaks[
        "hbm_bytes_per_s"]
    return 100.0 * least / t
