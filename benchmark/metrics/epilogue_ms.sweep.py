"""Host milliseconds of the epilogue per batch: ``dp/finalize`` less its
nested ``dp/finalize_transfer`` (which waits for the batched replay),
summed over the batch's configs, which the query plane finalizes on its
executor. A batch's stage times ride on its first item."""

from benchmark.metrics import _stages


def read(ctx):
    s = _stages.mean_over_items(ctx, _stages.host_epilogue_s)
    return None if s is None else s * 1e3
