"""Residency layer: routed (token, expert) lookups in the window that found
no slot, as a share of all lookups (the engine's hit and miss counters)."""


def read(ctx):
    total = ctx.counters["hits"] + ctx.counters["misses"]
    if not total:
        return None
    return 100.0 * ctx.counters["misses"] / total
