"""Device seconds of the chunk-kernel programs per aggregate, from the
profiler trace."""

from benchmark.metrics import _chunk_programs


def read(ctx):
    return _chunk_programs.device_s_per_aggregate(ctx)
