"""The least HBM traffic a YCSB batch needs, whatever implements it: every
operation's row is read once, every result row is written once, and every
distinct written row is written once, at `row_bytes` a row (1,000 B for a
record of 250 float32 words)."""


def batch_bytes(ops: int, distinct_writes: int, row_bytes: int) -> int:
    return (2 * ops + distinct_writes) * row_bytes
