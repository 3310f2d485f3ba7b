"""Bytes moved between host and device per PageRank round of the window, in
MB (1e6 B), from the execution backend's own counter
(`JaxBackend.transfer_bytes`, core/backend.py)."""
from program_metrics import counter_per


def read(ctx):
    return counter_per(ctx, "transfer_bytes",
                       ctx.after.get("rounds", 0) - ctx.before.get("rounds", 0), 1e-6)
