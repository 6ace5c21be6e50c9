"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    return None if ctx.trace is None else 100.0 * ctx.trace.idle_share
