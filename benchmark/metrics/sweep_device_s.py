"""Device seconds of the batched chunk program (``_chunk_step_rle_batch``
in ops/streaming.py: one wire chunk decoded once and bounded for every
lane of a batch) per batch, from the profiler trace."""

from benchmark.metrics import _sweep_bytes

PATTERNS = (r"^_chunk_step_rle_batch$",)


def read(ctx):
    if ctx.trace is None:
        return None
    n = _sweep_bytes.batches(ctx)
    s = ctx.trace.programs_matching(PATTERNS)
    return s / n if n and s > 0 else None
