"""Engine layer: mean host time of a fused prefill chunk in the window, in
ms: each ``step`` span of kind ``chunk`` less the ``pull`` spans of its
unit."""
from bench import spans


def read(ctx):
    return spans.host_ms(ctx.spans, "chunk")
