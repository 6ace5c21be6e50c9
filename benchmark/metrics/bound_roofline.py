"""Share of the chunk kernels' device time that HBM bandwidth alone would
need: bound_bytes / peak bytes per second / device seconds, in percent.
The work is bandwidth-bound (a handful of integer operations per byte),
so the bytes set the roofline."""

from benchmark.metrics import _chunk_programs, _roofline_bytes


def read(ctx):
    t = _chunk_programs.device_s_per_aggregate(ctx)
    if t is None:
        return None
    least = _roofline_bytes.bound_bytes(ctx.config) / ctx.peaks[
        "hbm_bytes_per_s"]
    return 100.0 * least / t
