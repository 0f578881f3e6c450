"""Engine layer: device programs the engine launched in the window (its
``device_dispatches`` counter) per output token."""


def read(ctx):
    if not ctx.tokens:
        return None
    return ctx.counters["device_dispatches"] / ctx.tokens
