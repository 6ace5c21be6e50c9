"""Set-up: from the process's start to the window's start. It holds the
interpreter and JAX start, data generation, loading or compiling every
program, and the traffic's own set-up (a warm-up aggregate, or ingest and
the bound-cache fill)."""


def read(ctx):
    return ctx.setup_s
