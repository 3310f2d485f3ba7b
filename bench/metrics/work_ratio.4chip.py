"""Balance of the mesh: the largest shard's work over the mean shard's, per
sharded stage of the window (`SpmdBackend.stage_stats[*].work_ratio()`),
averaged over the window's stages."""


def read(ctx):
    ratios = ctx.after.get("work_ratios", [])[len(ctx.before.get(
        "work_ratios", [])):]
    if not ratios:
        return None
    return sum(ratios) / len(ratios)
