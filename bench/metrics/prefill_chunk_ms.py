"""Model step, prefill: mean time from a prefill chunk's launch to the end of
its pull (the engine's ``launch`` and ``pull`` spans of ``chunk`` units) in
the window, in ms."""


def read(ctx):
    kind, start, end = {}, {}, {}
    for ph, name, _, _, t0, dur, unit, args in ctx.spans:
        if name == "unit":
            kind[unit] = (args or {}).get("kind")
        elif name == "launch":
            start.setdefault(unit, t0)
        elif name == "pull":
            end[unit] = t0 + dur
    times = [end[u] - start[u] for u, k in kind.items()
             if k == "chunk" and u in start and u in end]
    if not times:
        return None
    return 1e3 * sum(times) / len(times)
