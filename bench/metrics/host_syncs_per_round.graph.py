"""Host-device transfers per PageRank round of the window, from the
execution backend's own counter (`JaxBackend.host_syncs`)."""


def read(ctx):
    rounds = ctx.after["rounds"] - ctx.before["rounds"]
    if rounds <= 0:
        return None
    return (ctx.after["host_syncs"] - ctx.before["host_syncs"]) / rounds
