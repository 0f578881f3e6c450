"""Pallas kernels vs ref.py oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.slots import quantize_int8
from repro.kernels import ops, ref


@pytest.mark.parametrize("e,c,d,f,s", [(4, 8, 16, 32, 3), (6, 16, 32, 16, 6),
                                       (2, 4, 8, 8, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_slot_gmm_sweep(rng, e, c, d, f, s, dtype):
    x = jnp.asarray(rng.standard_normal((e, c, d)), dtype)
    w = jnp.asarray(rng.standard_normal((s + 1, d, f)), dtype)
    w = w.at[-1].set(0.0)
    lut = jnp.asarray(rng.integers(0, s + 1, e), jnp.int32)
    out = ops.slot_gmm(x, w, lut, block_c=4, block_f=8, block_d=8)
    r = ref.slot_gmm_ref(x, w, lut)
    atol = 1e-4 if dtype == jnp.float32 else 0.1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=atol)


def test_slot_gmm_int8(rng):
    e, c, d, f, s = 4, 8, 16, 24, 3
    x = jnp.asarray(rng.standard_normal((e, c, d)), jnp.float32)
    wf = rng.standard_normal((s + 1, d, f)).astype(np.float32)
    q = np.zeros((s + 1, d, f), np.int8)
    sc = np.zeros((s + 1, f), np.float32)
    for i in range(s):
        q[i], sc[i] = quantize_int8(wf[i])
    lut = jnp.asarray([0, 2, 1, 3], jnp.int32)
    out = ops.slot_gmm(x, jnp.asarray(q), lut, jnp.asarray(sc),
                       block_c=4, block_f=8, block_d=8)
    r = ref.slot_gmm_ref(x, jnp.asarray(q), lut, jnp.asarray(sc))
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=1e-4)


def test_moe_slot_ffn_matches_ref(rng):
    e, c, d, f, s = 4, 8, 16, 24, 5
    x = jnp.asarray(rng.standard_normal((e, c, d)), jnp.float32)
    slots = {
        "w_gate": jnp.asarray(rng.standard_normal((s + 1, d, f)), jnp.float32),
        "w_up": jnp.asarray(rng.standard_normal((s + 1, d, f)), jnp.float32),
        "w_down": jnp.asarray(rng.standard_normal((s + 1, f, d)), jnp.float32),
    }
    lut = jnp.asarray(rng.integers(0, s + 1, e), jnp.int32)
    out = ops.moe_slot_ffn(x, slots, lut, block_c=4, block_f=8, block_d=8)
    r = ref.moe_slot_ffn_ref(x, slots, lut)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r, np.float32),
                               atol=2e-4)


@pytest.mark.parametrize("sq,skv,h,hkv,dh", [(32, 32, 4, 2, 16), (64, 64, 2, 1, 8),
                                             (16, 48, 4, 4, 32)])
@pytest.mark.parametrize("kw", [dict(causal=True), dict(causal=False),
                                dict(causal=True, window=16),
                                dict(causal=True, soft_cap=15.0)])
def test_flash_attention_sweep(rng, sq, skv, h, hkv, dh, kw):
    q = jnp.asarray(rng.standard_normal((2, sq, h, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, skv, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, skv, hkv, dh)), jnp.float32)
    out = ops.flash_attention(q, k, v, block_q=16, block_kv=16, **kw)
    r = ref.flash_attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=2e-3)


def test_flash_attention_bf16(rng):
    q = jnp.asarray(rng.standard_normal((1, 32, 2, 16)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 32, 2, 16)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 32, 2, 16)), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, block_q=16, block_kv=16)
    r = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=5e-2)


@pytest.mark.parametrize("s,h,hkv,dh,bk", [(64, 4, 2, 16, 16), (128, 2, 1, 32, 32),
                                           (32, 8, 8, 8, 8)])
def test_decode_attention_sweep(rng, s, h, hkv, dh, bk):
    b = 3
    q = jnp.asarray(rng.standard_normal((b, h, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, dh)), jnp.float32)
    lengths = jnp.asarray(rng.integers(1, s + 1, b), jnp.int32)
    from repro.kernels.decode_attention import decode_attention

    out = decode_attention(q, k, v, lengths, block_kv=bk, interpret=True)
    r = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=2e-3)


@pytest.mark.parametrize("t,e,k", [(32, 8, 2), (64, 16, 4), (16, 128, 8)])
@pytest.mark.parametrize("normalize", [True, False])
def test_topk_gate_sweep(rng, t, e, k, normalize):
    logits = jnp.asarray(rng.standard_normal((t, e)), jnp.float32)
    ids, w = ops.topk_gate(logits, k, normalize=normalize)
    ri, rw = ref.topk_gate_ref(logits, k, normalize=normalize)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(w), np.asarray(rw), atol=1e-5)


def test_attention_model_path_uses_pallas(rng):
    """use_pallas=True wires the model's attention through the kernels and
    matches the jnp path."""
    from repro.config import AttentionConfig, ShardingConfig
    from repro.models import attention as A
    from repro.models.transformer import Runtime

    acfg = AttentionConfig(num_heads=4, num_kv_heads=2, head_dim=16)
    p = A.init_attention(jax.random.PRNGKey(0), 64, acfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((1, 64, 64)), jnp.float32)
    y_ref = A.attention_train(p, acfg, x, q_chunk=16, kv_chunk=16)
    y_pal = A.attention_train(p, acfg, x, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref), atol=2e-3)


def test_routing_parity_on_ties(rng):
    """Host routing, the Pallas topk_gate, the lax.top_k fallback, and the
    model's topk_route must pick IDENTICAL experts on tied logits (lowest
    index wins) — residency accounting depends on the three agreeing."""
    from repro.config import MoEConfig
    from repro.core.predictor import host_topk_route
    from repro.kernels.ops import route_topk
    from repro.models import moe as M

    t, e, k = 8, 16, 4
    logits = rng.standard_normal((t, e)).astype(np.float32)
    # manufacture exact ties, including a fully-constant row
    logits[:, 3] = logits[:, 7]
    logits[:, 11] = logits[:, 7]
    logits[0, :] = 0.5
    logits[5, :4] = logits[5, 4:8]
    lg = jnp.asarray(logits)

    ids_host, w_host = host_topk_route(logits, k)
    ids_auto, w_auto = route_topk(lg, k)                       # lax.top_k on CPU
    ids_pal, w_pal = ops.topk_gate(lg, k)                      # Pallas (interpret)
    ids_model, w_model, _ = M.topk_route(
        lg, MoEConfig(num_experts=e, top_k=k, expert_d_ff=8)
    )

    np.testing.assert_array_equal(ids_host, np.asarray(ids_auto))
    np.testing.assert_array_equal(ids_host, np.asarray(ids_pal))
    np.testing.assert_array_equal(ids_host, np.asarray(ids_model))
    np.testing.assert_allclose(w_host, np.asarray(w_auto), atol=1e-6)
    np.testing.assert_allclose(w_host, np.asarray(w_pal), atol=1e-6)


def test_kernels_interpret_only_on_cpu(monkeypatch, rng):
    """Pallas kernels interpret on the CPU backend and lower on TPU; any other
    backend is an error, never a silent interpreter run."""
    logits = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.topk_gate(logits, 2)
