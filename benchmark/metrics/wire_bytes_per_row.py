"""Bytes shipped to the device per input row: the program's
``ops/wire_bytes`` over ``ops/wire_rows`` counters (runtime/driver.py:
each host slab handed to the transfer, padding included, and the input
rows of its chunks), read from the process's counters when the reader
runs.

The counters are process-wide, and that is what makes the ratio the
cell's: a benchmark process aggregates only its cell's data, in the
warm-up and in the window alike, so every shipped window is one of the
cell's. A program without the counters reads nothing."""

WIRE_BYTES = "ops/wire_bytes"
WIRE_ROWS = "ops/wire_rows"


def read(ctx):
    from pipelinedp_tpu import profiler
    rows = profiler.event_count(WIRE_ROWS)
    return profiler.event_count(WIRE_BYTES) / rows if rows else None
