"""What the sweep's readers share: its batches, and the bytes its batched
bound-and-aggregate must move at least once.

The rows are read once as plain columns, as ``_roofline_bytes`` counts
them, and each lane of a batch writes its own accumulators once: one
per metric plus the privacy-unit count that selection reads. This counts
the work, not the batched kernel's sort operands or wire format.
"""

import json
import os

from benchmark.metrics import _roofline_bytes

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic", "sweep.json")


def lanes() -> int:
    """Configs per batch: one per l0 of the traffic's ladder."""
    with open(TRAFFIC) as f:
        return len(json.load(f)["l0"])


def least_bytes(cfg: dict) -> int:
    d, q = cfg["data"], cfg["aggregate"]
    accs = len(set(q["metrics"]) | {"PRIVACY_ID_COUNT"})
    return (d["n_rows"] * _roofline_bytes.ROW_BYTES
            + lanes() * d["n_partitions"] * _roofline_bytes.ACC_BYTES * accs)


def batches(ctx) -> int:
    """Completed batches of the window: a batch's items succeed or fail
    together."""
    return sum(1 for it in ctx.window.items if it.ok) // lanes()
