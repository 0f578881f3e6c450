"""Device: HBM in use once the window has closed (``bytes_in_use`` of the
device's allocator), in GB."""


def read(ctx):
    return ctx.memory["bytes_in_use"] / 1e9
