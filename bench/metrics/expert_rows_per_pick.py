"""Model step, prefill: the expert weight planes the fused chunks read per
routed pick, Σ rows / Σ picks over the window's ``telemetry`` spans that
carry both (the program's per-chunk count, taken on the device: one per
slot group the grouped expert GEMM runs, one per pick where a step gathers,
against layers × tokens × top-k picks). None where no span carries them."""


def read(ctx):
    rows = picks = 0
    for _, name, _, _, _, _, _, args in ctx.spans:
        if name == "telemetry" and args and "picks" in args and "rows" in args:
            rows += args["rows"]
            picks += args["picks"]
    return rows / picks if picks else None
