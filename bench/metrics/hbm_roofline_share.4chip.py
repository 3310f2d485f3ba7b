"""Share of the mesh's HBM roofline, in percent: the least time the
window's batches need at the peak HBM bandwidth of all the chips the trace
saw (kv_min_bytes.py, peaks.json) over the mean device busy time of the
traced window. hbm_roofline_share.kv divides by one chip's peak; on a mesh
every chip streams its own share of the rows."""
from kv_min_bytes import batch_bytes


def read(ctx):
    t, w = ctx.trace, ctx.work
    if t is None or t["busy_s"] <= 0 or not ctx.calls or "ops" not in w:
        return None
    chips = len(t["busy_s_by_device"])
    total = sum(batch_bytes(o, d, w["row_bytes"])
                for o, d in zip(w["ops"], w["distinct_writes"]))
    peak = chips * ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * total / peak / t["busy_s"]
