"""Whole runs of the harness at a tiny size on the CPU (the look for a chip
skipped), with the configuration stated in float32: a sound run comes out
correct, the control (the reference in bfloat16, one step below) put in the
program's place does not, and neither does a run whose timed path is
broken underneath: a served token altered where it is produced, or (where
the cell misses) missed experts dropped instead of corrected."""
import dataclasses

import numpy as np
import pytest

import benchpath  # noqa: F401
import tiny

from bench import harness

SEED = 2 ** 31 + 11          # seeds may need more than 32 signed bits


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """Nothing written into the checkout by a test."""
    monkeypatch.setattr(harness, "place_compile_cache", lambda: None)


def run(tmp_path, family, slots, decode, fault=None, control=None):
    wl = tiny.write_cell(str(tmp_path), family, slots, decode, dtype="float32",
                         limit=1e-4)
    return harness.run_cell(wl, SEED, 1.0, False, roots=[str(tmp_path), harness.BENCH],
                            chips_check=False, fault=fault, control=control)


def alter_token(eng):
    """Serve the vocabulary's worst token at every third decode step."""
    decode, calls = eng.decode, [0]

    def broken(logits, steps, **kw):
        out = decode(logits, steps, **kw)
        calls[0] += 1
        if calls[0] % 3 == 0:
            last = np.array(eng.last_logits)
            last[0, last[0].argmin()] = last[0].max() + 1
            eng.last_logits = last
        return out

    eng.decode = broken


def drop_misses(eng):
    eng.rescfg = dataclasses.replace(eng.rescfg, host_compute_misses=False)


CELLS = [("qwen3", 4, True), ("qwen15", 8, False)]


@pytest.mark.parametrize("family,slots", [("qwen3", 4), ("qwen15", 8)])
def test_sound_run_is_correct_and_control_is_not(tmp_path, family, slots):
    res = run(tmp_path, family, slots, True, control="lower")
    checks = res["checks"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert checks["served_compared"]["value"] > 20
    assert checks["control_widest_gap"]["value"] > checks["widest_gap"]["limit"]
    assert res["control_correct"] is False
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("family,slots,decode", CELLS)
def test_altered_token_is_not_correct(tmp_path, family, slots, decode):
    res = run(tmp_path, family, slots, decode, fault=alter_token)
    assert not res["correct"]
    assert res["checks"]["widest_gap"]["value"] > res["checks"]["widest_gap"]["limit"]


def test_dropped_misses_are_not_correct(tmp_path):
    res = run(tmp_path, "qwen3", 4, True, fault=drop_misses)
    assert not res["correct"]


def test_end_to_end_metrics_reported(tmp_path):
    res = run(tmp_path, "qwen15", 8, False)
    m = res["metrics"]
    assert m["output_tok_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
