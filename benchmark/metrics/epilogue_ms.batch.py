"""Host milliseconds of the epilogue per aggregate (ops/finalize.py,
partition selection and secure noise in float64 on the host): the
``dp/finalize`` stage less its device-to-host transfer, which in a cold
aggregate waits for the chunk programs."""

from benchmark.metrics import _stages


def read(ctx):
    s = _stages.mean_over_items(ctx, _stages.host_epilogue_s)
    return None if s is None else s * 1e3
