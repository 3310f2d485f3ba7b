"""Share of the HBM roofline, in percent: the least time the window's
batches need at the chip's peak HBM bandwidth (kv_min_bytes.py, peaks.json)
over the device busy time of the traced window."""
from kv_min_bytes import batch_bytes


def read(ctx):
    t, w = ctx.trace, ctx.work
    if t is None or t["busy_s"] <= 0 or not ctx.calls or "ops" not in w:
        return None
    total = sum(batch_bytes(o, d, w["row_bytes"])
                for o, d in zip(w["ops"], w["distinct_writes"]))
    return 100.0 * total / ctx.peaks["hbm_bytes_per_s"] / t["busy_s"]
