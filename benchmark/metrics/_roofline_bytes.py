"""Bytes the bound-and-aggregate work must move at least once.

The rows are read once as plain columns (int32 privacy unit, int32
partition, float32 value) and each partition accumulator the release
needs is written once as 4 bytes: one per metric plus the privacy-unit
count that selection reads. This counts the work, not one
implementation's sort operands or wire format.
"""

ROW_BYTES = 4 + 4 + 4
ACC_BYTES = 4


def bound_bytes(cfg: dict) -> int:
    d, q = cfg["data"], cfg["aggregate"]
    accs = set(q["metrics"]) | {"PRIVACY_ID_COUNT"}
    return d["n_rows"] * ROW_BYTES + d["n_partitions"] * ACC_BYTES * len(accs)
