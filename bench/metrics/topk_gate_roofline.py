"""Kernels: the fused softmax + top-k router gate (``kernels/topk_gate.py``)
against its roofline. Each ``topk_gate`` op of the device trace in the window
gives its logit tile's shape in its HLO text (the operand,
``custom-call(f32[T,E]``); the least time
for its operations and bytes (``bench/counts.py``) at the chip's peaks,
summed, over the summed device time of those ops."""
import re

from bench import counts

SHAPE = re.compile(r"custom-call\(f32\[(\d+),(\d+)\]")


def read(ctx):
    w0, w1 = ctx.trace_window
    least = spent = 0.0
    for name, a, b, detail in ctx.ops:
        if not (w0 <= a and b <= w1) or "topk_gate" not in name + detail:
            continue
        m = SHAPE.search(name + detail)
        if m is None:
            return None
        ops, byts = counts.topk_gate(int(m.group(1)), int(m.group(2)),
                                     ctx.shapes.top_k)
        least += counts.roofline_s(ops, byts, ctx.peak)
        spent += (b - a) * 1e-9
    if spent <= 0:
        return None
    return 100.0 * least / spent
