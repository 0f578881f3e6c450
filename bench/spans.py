"""Readings of the engine's own spans (``repro.obs.Tracer`` records:
``(ph, name, track, lane, t0, dur, unit, args)``, on ``perf_counter``)."""
from collections import defaultdict


def host_ms(spans, kind: str):
    """Mean over the ``step`` spans of ``kind`` of the step's duration less
    the summed ``pull`` spans of its unit, in ms: the engine's time per step
    with no program of its own in flight. None without such steps."""
    steps, pulls = {}, defaultdict(float)
    for _, name, _, _, _, dur, unit, args in spans:
        if name == "step" and (args or {}).get("kind") == kind:
            steps[unit] = dur
        elif name == "pull":
            pulls[unit] += dur
    if not steps:
        return None
    return 1e3 * sum(d - pulls[u] for u, d in steps.items()) / len(steps)
