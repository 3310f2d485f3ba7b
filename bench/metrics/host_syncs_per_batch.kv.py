"""Host-device transfers per batch of the window, from the execution
backend's own counter (`JaxBackend.host_syncs`, core/backend.py)."""


def read(ctx):
    if not ctx.calls:
        return None
    return (ctx.after["host_syncs"] - ctx.before["host_syncs"]) / ctx.calls
