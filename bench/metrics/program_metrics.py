"""Shared by the readers of the program's spans and counters: a sum over
the trace reduction's ``host_self_s_by_span`` (harness/spans.py), or a
counter's difference over the window, per batch or round. Each returns
None where the trace or the counters do not have what it reads."""


def self_ms_per(ctx, prefixes, units):
    by_span = (ctx.trace or {}).get("host_self_s_by_span")
    if not by_span or not units:
        return None
    picked = [s for n, s in by_span.items() if n.startswith(prefixes)]
    if not picked:
        return None
    return 1e3 * sum(picked) / units


def counter_per(ctx, key, units, scale=1.0):
    if key not in ctx.after or key not in ctx.before or not units:
        return None
    return scale * (ctx.after[key] - ctx.before[key]) / units
