"""Residency layer: expert bytes copied from host memory into the slots in
the window (the engine's ``bytes_uploaded`` counter), in MB per output
token."""


def read(ctx):
    if not ctx.tokens:
        return None
    return ctx.counters["bytes_uploaded"] / 1e6 / ctx.tokens
