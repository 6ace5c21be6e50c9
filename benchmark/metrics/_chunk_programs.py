"""The chunk-kernel layer's programs, by their jit names in the trace:
the streamed bound-and-aggregate steps of ops/streaming.py
(``_chunk_step``, ``_chunk_step_rle``, ``_chunk_step_rle_compact``, ...)
over ops/columnar.py, and the compact merge."""

PATTERNS = (r"^_chunk_step", r"^merge_compact_chunks$", r"^_merge_pending$")


def device_s_per_aggregate(ctx):
    if ctx.trace is None:
        return None
    n = sum(1 for it in ctx.window.items if it.ok)
    s = ctx.trace.programs_matching(PATTERNS)
    return s / n if n and s > 0 else None
