"""Device busy time per batch of the traced window, in ms: the union of the
device's operation intervals (any name) over the batches."""


def read(ctx):
    if ctx.trace is None or not ctx.calls:
        return None
    return 1e3 * ctx.trace["busy_s"] / ctx.calls
