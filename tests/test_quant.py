"""Grouped int4 quantization subsystem (repro.quant): tier-1 coverage.

Pack/unpack round-trip, batch-vs-single bit-equality (the upload path's
invariant), memoized lazy dequant, the in-kernel Pallas dequant path
(interpret mode on this host), link-bytes accounting, and end-to-end
exactness: int4 decode is exactness-clean WITHIN its format — greedy tokens
bit-identical across full residency, slot-starved rotary, and rotary+spec-K.

These are the tier-1 mirrors of the hypothesis properties in
``test_quant_properties.py`` (which skips without the dev deps).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import params_for
from repro.config import ResidencyConfig
from repro.core import RotaryEngine
from repro.core.residency import check_feasibility
from repro.core.slots import SlotStore, fake_quantized_batch, quantized_expert_bytes
from repro.models import init_params
from repro.models.transformer import Runtime
from repro.quant import (
    dequantize_int4,
    effective_group,
    int4_tensor_bytes,
    quantize_int4,
    quantize_int4_batch,
    unpack_int4,
)


# ===========================================================================
# pack / unpack / dequant
# ===========================================================================
def test_int4_roundtrip_error_bounded_by_group_scale(rng):
    """|dequant(quant(w)) - w| <= the group's scale step, everywhere."""
    for d, f, g in ((64, 48, 64), (48, 64, 64), (16, 8, 4), (6, 10, 64)):
        w = (rng.standard_normal((d, f)) * 3).astype(np.float32)
        packed, scale, mn = quantize_int4(w, g)
        back = np.asarray(
            dequantize_int4(jnp.asarray(packed), jnp.asarray(scale), jnp.asarray(mn))
        )
        step = np.repeat(scale.astype(np.float32), effective_group(d, g), axis=-2)
        assert (np.abs(back - w) <= step + 1e-6).all(), (d, f, g)


def test_int4_unpack_inverts_packing(rng):
    q = rng.integers(0, 16, (3, 12, 5)).astype(np.uint8)
    packed = (q[:, 0::2, :] | (q[:, 1::2, :] << 4)).astype(np.uint8)
    np.testing.assert_array_equal(np.asarray(unpack_int4(jnp.asarray(packed))), q)


def test_int4_batch_bit_equal_to_single(rng):
    """Quantizing N experts stacked must produce byte-identical packed
    buffers / scales / mins to quantizing each alone — the batched rotation
    upload relies on this (mirrors the int8 property)."""
    w = rng.standard_normal((5, 16, 12)).astype(np.float32)
    pb, sb, mb = quantize_int4_batch(w, 8)
    for i in range(5):
        p1, s1, m1 = quantize_int4(w[i], 8)
        np.testing.assert_array_equal(pb[i], p1)
        np.testing.assert_array_equal(sb[i], s1)
        np.testing.assert_array_equal(mb[i], m1)


def test_effective_group_clamps_to_axis():
    assert effective_group(2048, 64) == 64
    assert effective_group(48, 64) == 48
    assert effective_group(10, 4) == 2          # 4 doesn't divide 10
    with pytest.raises(AssertionError):
        effective_group(7, 4)                   # odd rows can't pack


# ===========================================================================
# SlotStore int4: bytes, memoized dequant, batched scatters
# ===========================================================================
def _shapes():
    return {"w_gate": (64, 48), "w_up": (64, 48), "w_down": (48, 64)}


def test_int4_store_bytes_le_030x_f16():
    """The acceptance ratio: packed nibbles + f16 group scale/min planes move
    <= 0.30x the bytes of an f16 slot per rotated expert."""
    shapes = _shapes()
    q4 = SlotStore(4, shapes, jnp.bfloat16, quantization="int4")
    fp = SlotStore(4, shapes, jnp.bfloat16)
    ratio = q4.bytes_per_expert / fp.bytes_per_expert
    assert ratio <= 0.30, ratio
    # analytic helper agrees with the store's real buffers
    assert q4.bytes_per_expert == sum(int4_tensor_bytes(s, 64) for s in shapes.values())
    assert quantized_expert_bytes(shapes, "int4", 2, 64) == q4.bytes_per_expert


def test_int4_write_batch_one_scatter_per_plane(rng):
    """A rotation moving N experts costs ONE fused scatter dispatch for all
    tensor planes together (packed + scale + min of every weight tensor),
    never one per expert or per plane."""
    store = SlotStore(4, _shapes(), jnp.float32, quantization="int4")
    w = {n: rng.standard_normal((3,) + s).astype(np.float32)
         for n, s in _shapes().items()}
    moved = store.write_batch([0, 1, 2], w)
    assert store.dispatches == 1
    assert moved == 3 * store.bytes_per_expert
    assert store.bytes_uploaded == moved


def test_int4_store_roundtrip_matches_host_dequant(rng):
    """What as_pytree returns for a written slot is exactly the host-side
    dequant of the quantized expert (the exactness contract)."""
    store = SlotStore(3, _shapes(), jnp.float32, quantization="int4")
    w = {n: rng.standard_normal((2,) + s).astype(np.float32)
         for n, s in _shapes().items()}
    store.write_batch([0, 2], w)
    tree = store.as_pytree()
    for n in _shapes():
        want = fake_quantized_batch(w[n], "int4", jnp.float32)
        np.testing.assert_array_equal(np.asarray(tree[n][0]), want[0])
        np.testing.assert_array_equal(np.asarray(tree[n][2]), want[1])
    raw = store.raw_pytree()
    assert {"min_w_gate", "scale_w_gate"} <= set(raw)


@pytest.mark.parametrize("quant", ["int8", "int4"])
def test_lazy_dequant_memoized_per_write_generation(rng, quant):
    """as_pytree dequantizes ONCE per write generation: repeated calls hit
    the cache, any write invalidates it."""
    store = SlotStore(4, _shapes(), jnp.float32, quantization=quant)
    w = {n: rng.standard_normal((1,) + s).astype(np.float32)
         for n, s in _shapes().items()}
    store.write_batch([0], w)
    t1 = store.as_pytree()
    for _ in range(3):
        assert store.as_pytree() is t1
    assert store.dequant_runs == 1
    store.write_batch([1], w)
    t2 = store.as_pytree()
    assert t2 is not t1
    assert store.as_pytree() is t2
    assert store.dequant_runs == 2


# ===========================================================================
# Pallas moe_gmm int4 path (interpret mode on this host)
# ===========================================================================
def test_slot_gmm_int4_matches_ref(rng):
    from repro.kernels import ops, ref

    e, c, d, f, s = 4, 8, 16, 24, 3
    x = jnp.asarray(rng.standard_normal((e * c, d)), jnp.float32)
    wf = rng.standard_normal((s + 1, d, f)).astype(np.float32)
    wf[-1] = 0.0
    packed, scale, mn = quantize_int4(wf, 8)
    sizes = jnp.full((e,), c, jnp.int32)            # c rows per expert
    planes = jnp.asarray([0, 2, 1, 3], jnp.int32)   # expert -> slot
    out = ops.slot_gmm(x, jnp.asarray(packed), sizes, planes, jnp.asarray(scale),
                       jnp.asarray(mn), block_m=4, block_k=8, block_n=8)
    r = ref.slot_gmm_ref(x, jnp.asarray(packed), sizes, planes,
                         jnp.asarray(scale), jnp.asarray(mn))
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=1e-4)


def test_moe_slot_ffn_int4_matches_ref(rng):
    from repro.kernels import moe_gmm, ref

    e, c, d, f, s = 4, 8, 16, 24, 5
    x = jnp.asarray(rng.standard_normal((e * c, d)), jnp.float32)
    slots = {}
    for name, shape in (("w_gate", (d, f)), ("w_up", (d, f)), ("w_down", (f, d))):
        wq = rng.standard_normal((s + 1,) + shape).astype(np.float32)
        p, sc, mn = quantize_int4(wq, 8)
        slots[name] = jnp.asarray(p)
        slots[f"scale_{name}"] = jnp.asarray(sc)
        slots[f"min_{name}"] = jnp.asarray(mn)
    sizes = jnp.full((e,), c, jnp.int32)
    planes = jnp.asarray(rng.integers(0, s + 1, e), jnp.int32)
    out = moe_gmm.moe_slot_ffn(x, slots, sizes, planes, interpret=True,
                               block_m=4, block_k=8, block_n=8)
    r = ref.moe_slot_ffn_ref(x, slots, sizes, planes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=2e-4)


# ===========================================================================
# end-to-end: int4 decode exactness + link accounting
# ===========================================================================
def _f32(arch="qwen2-moe-a2.7b"):
    cfg, _ = params_for(arch)
    cfg = dataclasses.replace(cfg, dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, mode, slots, **kw):
    return RotaryEngine(
        cfg, params,
        ResidencyConfig(mode=mode, num_slots=slots, prefetch_margin=2,
                        quantization="int4"),
        rt=Runtime(cache_len=64), batch=2, **kw,
    )


def test_int4_decode_exact_across_residency_modes(rng):
    """ACCEPTANCE: greedy tokens bit-identical between full residency,
    prefetch-covered rotary, slot-starved rotary (misses host-corrected
    against the dequantized weights), and rotary+spec-4 — all under
    quantization='int4'."""
    cfg, params = _f32()
    prompt = rng.integers(0, 200, (2, 8)).astype(np.int32)
    T = 10
    full = _engine(cfg, params, "full", 0)
    ref_toks = full.generate(prompt, T)
    covered = _engine(cfg, params, "rotary", cfg.moe.num_experts)
    np.testing.assert_array_equal(ref_toks, covered.generate(prompt, T))
    starved = _engine(cfg, params, "rotary", 5)
    np.testing.assert_array_equal(ref_toks, starved.generate(prompt, T))
    assert starved.stats.misses > 0          # quantized replay was exercised
    spec = _engine(cfg, params, "rotary", 5, spec_k=4)
    np.testing.assert_array_equal(ref_toks, spec.generate(prompt, T))
    assert spec.stats.replayed_steps > 0
    # every counted miss host-corrected (against dequantized weights)
    for eng in (starved, spec):
        s = eng.stats
        assert sum(l.host_computed for l in s.layers.values()) == s.misses


def test_int4_engine_shrinks_link_bytes(rng):
    """Same rotation workload, ~4x fewer bytes on the link: the int4 engine's
    per-expert upload is <= 0.30x the f16 cost, and bytes_uploaded threads
    through to EngineStats / summary()."""
    cfg, params = _f32()
    prompt = rng.integers(0, 200, (2, 8)).astype(np.int32)
    eng = _engine(cfg, params, "rotary", 5)
    eng.generate(prompt, 6)
    store = eng.manager.stores[0]
    f16_bytes = quantized_expert_bytes(
        {n: w.shape[1:] for n, w in eng.host_experts[0].items()}, None, dtype_bytes=2
    )
    assert store.bytes_per_expert / f16_bytes <= 0.30
    assert eng.stats.bytes_uploaded > 0
    assert eng.stats.bytes_uploaded == sum(
        st.bytes_uploaded for st in eng.manager.stores
    )
    assert "bytes_uploaded_MB" in eng.stats.summary()


def test_int4_feasibility_uses_packed_bytes():
    """check_feasibility prices slots at packed bytes: int4 < int8 < f16."""
    cfg, _ = params_for("qwen36-35b-a3b")
    reports = {
        q: check_feasibility(
            cfg, ResidencyConfig(mode="rotary", num_slots=6, quantization=q),
            batch=1, cache_len=64,
        )
        for q in (None, "int8", "int4")
    }
    assert reports["int4"].slot_bytes < reports["int8"].slot_bytes
    assert reports["int8"].slot_bytes < reports[None].slot_bytes
    assert reports["int4"].slot_bytes <= 0.30 * reports[None].slot_bytes


def test_serve_quantization_cli_mapping():
    """The CLI spells the default as 'none' (choices=[None, ...] made it
    impossible to type) and maps it back to ResidencyConfig's None."""
    from repro.launch.serve import QUANT_CHOICES

    assert QUANT_CHOICES == {"none": None, "int8": "int8", "int4": "int4"}
    for spelling, value in QUANT_CHOICES.items():
        ResidencyConfig(mode="rotary", num_slots=6, quantization=value)
    with pytest.raises(ValueError):
        ResidencyConfig(mode="rotary", num_slots=6, quantization="fp4")
