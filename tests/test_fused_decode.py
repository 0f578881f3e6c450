"""Fused whole-stack decode: exactness vs the seed walk, replay under forced
misses, O(1) dispatches per miss-free token, batched slot uploads, LUT patch
regression, ring-delta seam, prefill-rate admission EMA.

Chunked prefill hot path (PR 5): fused-chunk logits and post-prefill KV
bit-identical to the chunked layer walk across residency modes and slot
formats, dispatch bounds (one whole-stack launch + one queue-draining pull
per chunk), power-of-two chunk plans, and bucketed serving admission matching
the batch-1 splice-in path row for row."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import params_for
from repro.config import ResidencyConfig
from repro.core import RotaryEngine, SlotStore
from repro.core.engine import (
    build_fused_decode_step,
    build_fused_prefill_step,
    build_fused_window_step,
)
from repro.core.rotation import RotaryRing
from repro.models import init_params
from repro.models import transformer as tfm
from repro.models.transformer import Runtime
from repro.serving.scheduler import Scheduler


def _f32_setup():
    cfg, _ = params_for("qwen2-moe-a2.7b")
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, mode, slots, **kw):
    return RotaryEngine(
        cfg, params, ResidencyConfig(mode=mode, num_slots=slots, prefetch_margin=2),
        rt=Runtime(cache_len=64), batch=2, **kw,
    )


def test_fused_matches_host_routing_with_forced_misses(rng):
    """Greedy tokens bit-identical to the seed-style per-layer baseline under
    every residency mode, INCLUDING a slot-starved rotary engine whose misses
    force the suffix replay, and LRU (which decodes via the sync walk)."""
    cfg, params = _f32_setup()
    prompt = rng.integers(0, 200, (2, 8)).astype(np.int32)
    outs, engines = {}, {}
    for mode, slots in (("full", 0), ("rotary", 5), ("lru", 5), ("static", 5)):
        base = _engine(cfg, params, mode, slots, host_routing=True)
        eng = _engine(cfg, params, mode, slots)
        outs[mode] = (base.generate(prompt, 10), eng.generate(prompt, 10))
        engines[mode] = eng
    for mode, (ref, got) in outs.items():
        np.testing.assert_array_equal(ref, got, err_msg=mode)
    # the fused path actually ran where it should, and replay was exercised
    assert engines["full"]._fused_decode and engines["rotary"]._fused_decode
    assert not engines["lru"]._fused_decode
    assert engines["rotary"].stats.replayed_steps > 0
    assert engines["rotary"].stats.misses > 0
    # every counted miss was host-corrected (mechanism parity with the walk)
    s = engines["rotary"].stats
    assert sum(l.host_computed for l in s.layers.values()) == s.misses


def test_fused_one_pull_and_one_dispatch_per_token(rng):
    """Miss-free fused decode: exactly ONE queue-draining device->host pull
    AND one compiled-program launch per token — O(1), not O(layers). The
    per-layer hot path issues >= 2 launches per MoE layer per token."""
    cfg, params = _f32_setup()
    prompt = rng.integers(0, 200, (2, 8)).astype(np.int32)
    steps = 6

    fused = _engine(cfg, params, "full", 0)
    logits = fused.prefill(prompt)
    pulls0, disp0 = fused.stats.sync_pulls, fused.stats.device_dispatches
    fused.decode(logits, steps)
    assert fused.stats.sync_pulls - pulls0 == steps
    assert fused.stats.device_dispatches - disp0 == steps
    assert fused.stats.misses == 0

    layer = _engine(cfg, params, "full", 0, fused_decode=False)
    logits = layer.prefill(prompt)
    disp0 = layer.stats.device_dispatches
    layer.decode(logits, steps)
    assert layer.stats.device_dispatches - disp0 >= 2 * cfg.num_layers * steps


def test_fused_decode_flag_validation():
    cfg, params = _f32_setup()
    with pytest.raises(AssertionError):
        _engine(cfg, params, "lru", 5, fused_decode=True)
    with pytest.raises(AssertionError):
        _engine(cfg, params, "rotary", 5, host_routing=True, fused_decode=True)


def test_lut_patch_at_most_one_dispatch_per_layer_per_step(rng):
    """Regression (perf): steady-state rotation issues AT MOST one LUT patch
    dispatch per MoE layer per decode step — the persistent device LUT is
    patched incrementally, never re-uploaded per layer."""
    cfg, params = _f32_setup()
    prompt = rng.integers(0, 200, (2, 8)).astype(np.int32)
    eng = _engine(cfg, params, "rotary", 5)
    logits = eng.prefill(prompt)
    patches0 = eng.stats.lut_patch_dispatches
    steps = 8
    eng.decode(logits, steps)
    # replayed steps re-read the (clean) LUT and must not add patches
    assert eng.stats.lut_patch_dispatches - patches0 <= cfg.num_layers * steps


def test_write_batch_matches_per_expert_writes():
    """One fused scatter per write_batch == N per-expert writes, bit-for-bit,
    with ONE dispatch for every tensor together (and donation-safe)."""
    rng = np.random.default_rng(0)
    shapes = {"w_up": (8, 12), "w_down": (12, 8)}
    experts = [rng.standard_normal((8, 12)).astype(np.float32) for _ in range(3)]
    downs = [rng.standard_normal((12, 8)).astype(np.float32) for _ in range(3)]

    one = SlotStore(4, shapes, jnp.float32)
    for i, slot in enumerate((0, 2, 3)):
        one.write(slot, {"w_up": experts[i], "w_down": downs[i]})

    bat = SlotStore(4, shapes, jnp.float32)
    d0 = bat.dispatches
    moved = bat.write_batch(
        [0, 2, 3],
        {"w_up": np.stack(experts), "w_down": np.stack(downs)},
        donate=True,
    )
    assert bat.dispatches - d0 == 1          # one fused scatter for ALL tensors
    assert moved == 3 * (8 * 12 + 12 * 8) * 4
    for name in shapes:
        np.testing.assert_array_equal(
            np.asarray(one.buffers[name]), np.asarray(bat.buffers[name])
        )


def test_write_batch_int8_matches_single_quantization():
    rng = np.random.default_rng(1)
    shapes = {"w_up": (6, 10)}
    ws = [rng.standard_normal((6, 10)).astype(np.float32) for _ in range(2)]
    one = SlotStore(3, shapes, jnp.bfloat16, quantization="int8")
    for i, slot in enumerate((1, 2)):
        one.write(slot, {"w_up": ws[i]})
    bat = SlotStore(3, shapes, jnp.bfloat16, quantization="int8")
    bat.write_batch([1, 2], {"w_up": np.stack(ws)})
    np.testing.assert_array_equal(
        np.asarray(one.buffers["w_up"]), np.asarray(bat.buffers["w_up"])
    )
    np.testing.assert_array_equal(
        np.asarray(one.scales["w_up"]), np.asarray(bat.scales["w_up"])
    )


def test_ring_delta_seam_minimal_signed():
    """Tier-1 mirror of the hypothesis seam property (satellite fix): the
    cyclical-return delta wraps at the ring seam instead of reporting E-1."""
    e = 12
    assert RotaryRing._ring_delta(0, e - 1, e) == -1
    assert RotaryRing._ring_delta(e - 1, 0, e) == 1
    for src in range(e):
        for dst in range(e):
            d = RotaryRing._ring_delta(src, dst, e)
            assert (src + d) % e == dst
            assert abs(d) <= e // 2


def test_scheduler_prefill_rate_ema():
    """Admission no longer hard-codes prefill at 4x decode rate: the engine's
    measured prefill tok/s feedback moves the estimate (and the decision)."""
    from repro.serving.scheduler import Scheduler

    sch = Scheduler(2, est_tok_s=10.0)
    assert sch.est_prefill_tok_s == 40.0          # cold-start prior only
    # long prompt, tight deadline: rejected under the cold-start estimate
    r = sch.submit(np.zeros(400, np.int32), max_new=1, now=0.0, deadline_s=5.0)
    assert r.truncated and r.done
    sch.observe_prefill_rate(1000.0)
    sch.observe_prefill_rate(1000.0)
    assert sch.est_prefill_tok_s > 200.0
    r2 = sch.submit(np.zeros(400, np.int32), max_new=1, now=0.0, deadline_s=5.0)
    assert not r2.truncated                       # now admissible


# ===========================================================================
# chunked prefill hot path
# ===========================================================================
def _stacked_kv(eng):
    """Engine decode state as one stacked pytree, whichever layout it keeps."""
    if getattr(eng, "_dstate", None) is not None:
        return eng._dstate
    return eng._stack_state(eng.state)


def _chunk_engines(cfg, params, mode, slots, quant=None, chunk=8):
    def mk(**kw):
        return RotaryEngine(
            cfg, params,
            ResidencyConfig(mode=mode, num_slots=slots, prefetch_margin=2,
                            quantization=quant),
            rt=Runtime(cache_len=64), batch=2, **kw,
        )

    return mk(prefill_chunk=chunk), mk(prefill_chunk=chunk, fused_decode=False)


def test_chunked_prefill_exactness(rng):
    """The tentpole invariant: fused chunked prefill (ONE launch per chunk)
    produces logits AND post-prefill KV bit-identical to the chunked layer
    walk, across full / prefetch-covered rotary / slot-starved rotary (the
    starved case forces per-chunk suffix replay), and the greedy continuation
    matches the legacy full-sequence prefill token for token."""
    cfg, params = _f32_setup()
    prompt = rng.integers(0, 200, (2, 21)).astype(np.int32)   # plan [8,8,4,1]
    for mode, slots in (("full", 0), ("rotary", 8), ("rotary", 5)):
        fused, walk = _chunk_engines(cfg, params, mode, slots)
        lg_f = fused.prefill(prompt)
        lg_w = walk.prefill(prompt)
        np.testing.assert_array_equal(lg_f, lg_w, err_msg=f"{mode}/{slots}")
        for a, b in zip(
            jax.tree.leaves(_stacked_kv(fused)), jax.tree.leaves(_stacked_kv(walk))
        ):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"KV {mode}/{slots}"
            )
        legacy = RotaryEngine(
            cfg, params,
            ResidencyConfig(mode=mode, num_slots=slots, prefetch_margin=2),
            rt=Runtime(cache_len=64), batch=2,
        )
        o_legacy = legacy.generate(prompt, 8)
        np.testing.assert_array_equal(o_legacy, fused.decode(lg_f, 8))
        np.testing.assert_array_equal(o_legacy, walk.decode(lg_w, 8))
        if (mode, slots) == ("rotary", 5):
            # the starved case actually exercised the chunk replay machinery
            assert fused.stats.prefill_replays > 0
            assert fused.stats.misses > 0


@pytest.mark.parametrize("quant", ["int8", "int4"])
def test_chunked_prefill_exactness_quantized(rng, quant):
    """Same bit-identity on quantized slot stores, in the slot-starved regime
    whose misses replay against the dequantized weights (and the covered
    regime as a miss-free control)."""
    cfg, params = _f32_setup()
    prompt = rng.integers(0, 200, (2, 13)).astype(np.int32)
    for mode, slots in (("rotary", 8), ("rotary", 5)):
        fused, walk = _chunk_engines(cfg, params, mode, slots, quant=quant)
        lg_f = fused.prefill(prompt)
        lg_w = walk.prefill(prompt)
        np.testing.assert_array_equal(lg_f, lg_w, err_msg=f"{quant}/{slots}")
        for a, b in zip(
            jax.tree.leaves(_stacked_kv(fused)), jax.tree.leaves(_stacked_kv(walk))
        ):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"KV {quant}/{slots}"
            )
        np.testing.assert_array_equal(fused.decode(lg_f, 6), walk.decode(lg_w, 6))
    assert fused.stats.prefill_replays > 0          # starved case replayed


def test_chunked_prefill_dispatch_counts(rng):
    """Miss-free fused chunked prefill: exactly ONE whole-stack launch and
    ONE queue-draining pull per chunk, zero replays."""
    from repro.core.engine import prefill_chunk_plan

    cfg, params = _f32_setup()
    prompt = rng.integers(0, 200, (2, 21)).astype(np.int32)
    eng = _engine(cfg, params, "full", 0, prefill_chunk=8)
    pulls0 = eng.stats.sync_pulls
    eng.prefill(prompt)
    n = len(prefill_chunk_plan(21, 8))
    assert eng.stats.prefill_chunks == n
    assert eng.stats.sync_pulls - pulls0 == n
    assert eng.stats.prefill_replays == 0
    assert eng.stats.misses == 0


def test_prefill_chunk_plan():
    """Chunk plans are power-of-two lengths summing to the prompt, with the
    steady-state chunk repeated and a descending power-of-two tail (bounded
    compile cache)."""
    from repro.core.engine import prefill_chunk_plan

    assert prefill_chunk_plan(21, 8) == [8, 8, 4, 1]
    assert prefill_chunk_plan(64, 16) == [16, 16, 16, 16]
    assert prefill_chunk_plan(1, 64) == [1]
    for s in (1, 7, 16, 21, 100, 257):
        for c in (1, 4, 32):
            plan = prefill_chunk_plan(s, c)
            assert sum(plan) == s
            assert all(p & (p - 1) == 0 for p in plan)
            assert all(p <= c for p in plan)
    with pytest.raises(AssertionError):
        prefill_chunk_plan(8, 6)                    # chunk not a power of two


def test_chunked_prefill_flag_validation():
    """KV-only window-free stacks enable both chunked paths; a non-power-of-
    two chunk length is rejected up front."""
    cfg, params = _f32_setup()
    eng = _engine(cfg, params, "full", 0, prefill_chunk=8)
    assert eng._chunk_prefill_ok and eng._chunk_prefill_fused_ok
    with pytest.raises(AssertionError):
        _engine(cfg, params, "full", 0, prefill_chunk=6)   # not a power of two


def test_bucketed_admission_matches_batch1(rng):
    """The serving tentpole: admission through the shared compiled bucketed
    program (rows padded to the engine batch, spliced with the ragged
    machinery) emits the same per-request outputs as the batch-1 splice-in
    path — dense arch and rotary-residency MoE arch alike."""
    from repro.serving import ServingEngine

    for arch, res in (
        ("starcoder2-3b", None),
        ("qwen2-moe-a2.7b", ResidencyConfig(mode="rotary", num_slots=5)),
    ):
        cfg, params = params_for(arch)
        prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
                   for n in (5, 9, 12)]
        outs = {}
        for bucketed in (False, True):
            eng = ServingEngine(
                cfg, params, rt=Runtime(cache_len=64), num_slots=2,
                residency=res, bucketed_prefill=bucketed,
            )
            reqs = [eng.submit(p, max_new=5) for p in prompts]
            eng.run()
            outs[bucketed] = [r.output for r in reqs]
        assert outs[True] == outs[False], arch


def test_scheduler_prefill_bucket():
    """The scheduler owns the admission bucket: power-of-two cover of the
    longest admitted prompt, floored at 16 and clamped to the cache (over-
    capacity prompts never reach bucketing — submit rejects them)."""
    assert Scheduler.prefill_bucket([5], 256) == 16
    assert Scheduler.prefill_bucket([5, 17], 256) == 32
    assert Scheduler.prefill_bucket([64], 256) == 64
    assert Scheduler.prefill_bucket([1], 256) == 16
    # a prompt longer than the cache is rejected at submit time instead of
    # crashing mid-tick on the clamped bucket
    sch = Scheduler(2, max_prompt_len=64)
    r = sch.submit(np.zeros(65, np.int32), max_new=1, now=0.0)
    assert r.done and r.truncated and r in sch.rejected
    r2 = sch.submit(np.zeros(64, np.int32), max_new=1, now=0.0)
    assert not r2.done


def test_serving_feeds_prefill_rate(rng):
    """ServingEngine reports measured prefill rates to the scheduler — but
    only steady-state samples: a cold bucket's compile time must not poison
    the admission EMA."""
    from repro.serving import ServingEngine

    cfg, params = params_for("qwen2-moe-a2.7b")
    eng = ServingEngine(
        cfg, params, rt=Runtime(cache_len=32), num_slots=1,
        residency=ResidencyConfig(mode="rotary", num_slots=5),
    )
    default = eng.scheduler.est_prefill_tok_s
    # same prompt length -> same bucket: first prefill compiles (no sample)
    eng.submit(rng.integers(0, cfg.vocab_size, 6), max_new=2)
    eng.run()
    after_cold = eng.scheduler.est_prefill_tok_s
    assert after_cold == default
    eng.submit(rng.integers(0, cfg.vocab_size, 6), max_new=2)
    eng.run()
    assert eng.scheduler.est_prefill_tok_s != after_cold


# ===========================================================================
# asynchronous predictive prefetch: double-buffered slot generations
# ===========================================================================
def test_prefetch_flag_validation():
    """prefetch=True fails LOUDLY on combos with no in-flight launch to hide
    shadow uploads under, instead of silently running synchronous."""
    cfg, params = _f32_setup()
    with pytest.raises(ValueError, match="host_routing"):
        _engine(cfg, params, "rotary", 5, host_routing=True, prefetch=True)
    with pytest.raises(ValueError, match="fused"):
        _engine(cfg, params, "rotary", 5, fused_decode=False, prefetch=True)
    with pytest.raises(ValueError, match="fused"):
        _engine(cfg, params, "lru", 5, prefetch=True)


@pytest.mark.parametrize("mode,slots,quant,spec_k", [
    ("rotary", 5, None, 1),        # slot-starved: misses relaunch/replay
    ("rotary", 8, None, 1),        # prefetch-covered (all experts fit)
    ("full", 0, None, 1),          # never rotates: flag accepted, no shadow
    ("rotary", 5, None, 4),        # speculative windows over the flip
    ("rotary", 5, "int4", 1),      # grouped-int4 shadow planes
])
def test_prefetch_tokens_identical_to_sync(rng, mode, slots, quant, spec_k):
    """Greedy tokens with prefetch=True (shadow-generation uploads during the
    in-flight launch, boundary confirm/correct/flip, compiled-step miss
    relaunch) are bit-identical to the synchronous-rotation engine — across
    residency regimes, spec windows, and the int4 slot format."""
    cfg, params = _f32_setup()
    res = lambda: ResidencyConfig(mode=mode, num_slots=slots,
                                  quantization=quant)
    prompt = rng.integers(0, 200, (2, 7)).astype(np.int32)
    kw = dict(rt=Runtime(cache_len=64), batch=2, spec_k=spec_k)
    ref = RotaryEngine(cfg, params, res(), **kw).generate(prompt, 9)
    eng = RotaryEngine(cfg, params, res(), prefetch=True, **kw)
    np.testing.assert_array_equal(ref, eng.generate(prompt, 9))
    if mode == "rotary" and slots == 5:
        s = eng.stats
        assert s.misses > 0                     # starvation actually happened
        # every miss was resolved by the compiled-step relaunch or, past the
        # iteration cap, the replay fallback — never silently dropped
        assert s.relaunched_steps + s.replayed_steps > 0


# ===========================================================================
# stacked slot planes read at (layer, slot)
# ===========================================================================
def _walk_step(cfg, params, rt, tokens, state, cur_len, mode, planes, lut):
    """One step as a per-layer walk: every MoE layer reads its own
    [S+1, ...] plane and LUT row, the unfused path's plane rank."""
    block = jax.jit(
        lambda p, x, st, cl, res: tfm._apply_block(
            "attn_moe", p, cfg, rt, x, mode, st, cl, res
        )
    )
    x = jax.jit(lambda p, t: tfm.embed_tokens(cfg, p, t))(params, tokens)
    unit_p, unit_s = params["segments"][0][0], state[0][0]
    new_s, miss = [], []
    for layer in range(lut.shape[0]):
        at = lambda a: a[layer]
        res = {"slots": jax.tree.map(at, planes), "lut": lut[layer]}
        x, ns, aux = block(
            jax.tree.map(at, unit_p), x, jax.tree.map(at, unit_s), cur_len, res
        )
        new_s.append(ns)
        miss.append(aux["route_miss"])
    logits = jax.jit(
        lambda p, h: tfm.lm_logits(cfg, p, h[:, -1:])[:, 0]
    )(params, x)
    state = ((jax.tree.map(lambda *a: jnp.stack(a), *new_s),),)
    return logits, state, jnp.stack(miss)


def test_fused_steps_read_each_layers_slot_rows(rng):
    """The fused steps close over the stacked slot planes and read each
    routed row at (layer, slot). Three MoE layers hold different resident
    experts in planes that differ per layer, and most picks fall on the miss
    slot: fused chunk, decode and window logits and ``route_miss`` equal the
    per-layer walk bitwise, so a wrong layer index or miss row shows."""
    cfg, _ = params_for("qwen2-moe-a2.7b")
    cfg = dataclasses.replace(
        cfg, dtype="float32", segments=((("attn_moe",), 3),)
    )
    params = init_params(cfg, jax.random.PRNGKey(3))
    rt = Runtime(cache_len=32)
    n_layers, n_exp, n_slots = 3, cfg.moe.storage_experts, 3
    experts = params["segments"][0][0]["moe"]["experts"]
    lut = np.full((n_layers, n_exp), n_slots, np.int32)
    rows = np.zeros((n_layers, n_slots + 1), np.int32)
    for layer in range(n_layers):
        resident = rng.permutation(n_exp)[:n_slots]
        lut[layer, resident] = np.arange(n_slots)
        rows[layer, :n_slots] = resident
    zero_row = np.arange(n_slots + 1) == n_slots
    planes = {
        n: jnp.where(
            zero_row[None, :, None, None], 0.0,
            jnp.take_along_axis(w, rows[:, :, None, None], axis=1),
        )
        for n, w in experts.items()
    }
    lut = jnp.asarray(lut)
    residency = ({"slots": planes, "lut": lut},)
    state = tfm.zero_state(cfg, 2, rt.cache_len)
    prompt = jnp.asarray(rng.integers(0, 200, (2, 4)), jnp.int32)

    chunk = build_fused_prefill_step(
        cfg, rt, with_demand=False, donate_state=False
    )
    lg_f, st_f, aux = chunk(params, None, prompt, state, jnp.int32(0), residency)
    lg_w, st_w, miss_w = _walk_step(
        cfg, params, rt, prompt, state, jnp.int32(0), "chunk", planes, lut
    )
    np.testing.assert_array_equal(lg_f, lg_w)
    np.testing.assert_array_equal(aux["route_miss/seg0"], miss_w)
    for a, b in zip(jax.tree.leaves(st_f), jax.tree.leaves(st_w)):
        np.testing.assert_array_equal(a, b)
    assert 0 < int(miss_w.sum()) < miss_w.size      # hits and misses both

    tok = jnp.argmax(lg_w, axis=-1).astype(jnp.int32)
    decode = build_fused_decode_step(cfg, rt, with_demand=False, donate_state=False)
    lg_f, _, aux = decode(params, None, tok, st_w, jnp.int32(4), residency)
    lg_w, _, miss_w = _walk_step(
        cfg, params, rt, tok[:, None], st_w, jnp.int32(4), "decode", planes, lut
    )
    np.testing.assert_array_equal(lg_f, lg_w)
    np.testing.assert_array_equal(aux["route_miss/seg0"], miss_w)

    k_steps = 3
    window = build_fused_window_step(
        cfg, rt, k_steps, with_demand=False, donate_state=False
    )
    draft, last, _, aux = window(params, None, tok, st_w, jnp.int32(4), residency)
    st, cur = st_w, tok
    for j in range(k_steps):
        lg_w, st, miss_w = _walk_step(
            cfg, params, rt, cur[:, None], st, jnp.int32(4 + j), "decode",
            planes, lut,
        )
        cur = jnp.argmax(lg_w, axis=-1).astype(jnp.int32)
        np.testing.assert_array_equal(draft[j], cur)
        np.testing.assert_array_equal(aux["route_miss/seg0"][j], miss_w)
    np.testing.assert_array_equal(last, lg_w)
