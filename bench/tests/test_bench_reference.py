"""The plain reference against the rotary engine's prefill and decode logits
at a small size on the CPU, with starved slots and with a slot for every
expert. Both sides run in float32 here, so they agree to rounding; a wrong
equation on either side (a norm, the rope, the routing renormalisation, the
shared expert's gate) moves the logits by far more."""
import numpy as np
import pytest

import benchpath  # noqa: F401
import tiny

from bench import program, weights
from bench.reference import moe_lm
from bench.shapes import Shapes


def engine_logits(family, slots, prompt, steps):
    conf = tiny.conf(family, "float32")
    s = Shapes.of(conf)
    cfg = program.model_config(conf)
    w = weights.make_weights(s, 20240917, rows=program.expert_rows(cfg),
                             dtype="float32")
    eng = program.build_engine(cfg, program.params(cfg, w), tiny.cell(slots), 0)
    logits = [np.asarray(eng.prefill(prompt[None]), np.float32)[0]]
    for _ in range(steps):
        eng.decode(logits[-1][None], 1)
        logits.append(np.asarray(eng.last_logits, np.float32)[0])
    return s, w, eng, np.stack(logits)


@pytest.mark.parametrize("family", ["qwen3", "qwen15"])
@pytest.mark.parametrize("slots", [4, 8])
def test_reference_matches_engine(family, slots):
    prompt = np.random.default_rng(3).integers(0, 256, 12).astype(np.int32)
    s, w, eng, got = engine_logits(family, slots, prompt, steps=5)
    served = got.argmax(-1)
    seq = np.concatenate([prompt, served[:-1]])
    hid = moe_lm.hidden(s, w, [seq])[0]
    ref = np.asarray(hid @ w["lm_head"].astype(np.float32))[len(prompt) - 1:]
    assert got.shape == ref.shape == (6, s.vocab)
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= 1e-4 * scale
    gaps = moe_lm.served_gaps(s, w, [{"prompt": prompt, "served": served}])
    assert gaps.shape == (6,) and gaps.max() <= 1e-4 * scale
    if slots == 4:
        assert eng.stats.misses > 0        # the starved case did miss
    else:
        assert eng.stats.misses == 0


def test_reference_differs_without_shared_expert_gate():
    """A departure the reference guards against: Qwen1.5's shared expert is
    scaled by sigmoid(x @ shared_gate); dropping the gate moves the logits."""
    s = Shapes.of(tiny.conf("qwen15", "float32"))
    w = weights.make_weights(s, 5, dtype="float32")
    seq = np.arange(20, dtype=np.int32)
    a = np.asarray(moe_lm.hidden(s, w, [seq])[0])
    w["layers"]["shared_gate"] = w["layers"]["shared_gate"] * 0 + 100.0
    b = np.asarray(moe_lm.hidden(s, w, [seq])[0])
    assert np.abs(a - b).max() > 1e-2
