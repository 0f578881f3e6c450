"""The traffic generator repeats for a seed and differs across seeds, and the
command refuses to report without a chip."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import benchpath  # noqa: F401

from bench import harness, loadgen

MIXES = ["chat"]
BIG = 2 ** 31 + 2 ** 40 + 5


@pytest.mark.parametrize("mix", MIXES)
def test_prompts_repeat_bitwise_for_one_seed(mix):
    t = harness.load_json("traffic", mix)
    for seed in (0, 7, BIG):
        a = [loadgen.prompt(t, 151936, seed, i) for i in range(4)]
        b = [loadgen.prompt(t, 151936, seed, i) for i in range(4)]
        assert all(np.array_equal(x, y) and x.dtype == np.int32 for x, y in zip(a, b))
        assert all(len(x) == t["prompt_len"] for x in a)


@pytest.mark.parametrize("mix", MIXES)
def test_prompts_differ_across_seeds_and_requests(mix):
    t = harness.load_json("traffic", mix)
    a, b = loadgen.prompt(t, 151936, BIG, 0), loadgen.prompt(t, 151936, BIG + 1, 0)
    assert not np.array_equal(a, b)
    assert not np.array_equal(a, loadgen.prompt(t, 151936, BIG, 1))
    assert a.min() >= 0 and a.max() < 151936


def test_run_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(benchpath.CHECKOUT, "bench", "run.py"),
         "--workload", "qwen1.5-moe-a2.7b-4L.chat.resident", "--seed", str(BIG),
         "--seconds", "1", "--trace", "0"],
        cwd=benchpath.CHECKOUT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "TPU" in proc.stderr
    assert not proc.stdout.strip()
    with pytest.raises(json.JSONDecodeError):
        json.loads(proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else "")
