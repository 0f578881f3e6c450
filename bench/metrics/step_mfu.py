"""Model step: the whole window's share of the chip's peak. For every launch
served in the window (each prefill chunk, each decode step) the least time
the chip could take, the larger of its FLOPs over peak FLOP/s and its bytes
over peak bytes/s (``bench/counts.py``), summed and divided by the window's
seconds."""
from bench import counts


def read(ctx):
    if not ctx.work or ctx.window_s <= 0:
        return None
    least = sum(counts.roofline_s(f, b, ctx.peak) for f, b in ctx.work)
    return 100.0 * least / ctx.window_s
