"""Residency layer: host time of the between-step rotations in the window
(the summed ``rotation`` spans, their ``upload`` spans inside them), in ms
per output token."""


def read(ctx):
    rotations = [dur for _, name, _, _, _, dur, _, _ in ctx.spans
                 if name == "rotation"]
    if not rotations or not ctx.tokens:
        return None
    return 1e3 * sum(rotations) / ctx.tokens
