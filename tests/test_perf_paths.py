"""Correctness of the §Perf execution paths (SP attention, EP decode) against
their plain counterparts on a degenerate 1x1 mesh (shard_map semantics without
multi-device hardware; multi-device behaviour is covered by the dry-run)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.config import AttentionConfig, MoEConfig, ShardingConfig
from repro.models import attention as A
from repro.models import moe as M


def test_sp_attention_offsets_match_full(rng):
    """chunked_attention with a traced q_offset (the SP building block) over
    sequence slices reproduces the full computation slice by slice."""
    b, s, h, hkv, dh = 1, 64, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, dh)), jnp.float32)
    full = A.chunked_attention(q, k, v, q_chunk=16, kv_chunk=16)
    parts = []
    for i in range(4):                     # 4 "peers", 16 query positions each
        off = jnp.int32(i * 16)
        parts.append(
            A.chunked_attention(q[:, i * 16 : (i + 1) * 16], k, v,
                                q_chunk=16, kv_chunk=16, q_offset=off)
        )
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(parts, axis=1)), np.asarray(full), atol=2e-5
    )


def test_sp_attention_model_path(rng):
    """_sp_attention under a (1,1) mesh == attention_train."""
    from repro.models.transformer import Runtime, _sp_attention

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    acfg = AttentionConfig(num_heads=3, num_kv_heads=1, head_dim=8)  # 3 % 1 == 0 but force path
    p = A.init_attention(jax.random.PRNGKey(0), 24, acfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 32, 24)), jnp.float32)
    rt = Runtime(sharding=ShardingConfig(), mesh=mesh, q_chunk=8, kv_chunk=8)
    y_sp, cache = jax.jit(
        lambda xx: _sp_attention(p, acfg, None, rt, xx, 32)
    )(x)
    y_ref, cache_ref = A.attention_prefill(p, acfg, x, 32, q_chunk=8, kv_chunk=8)
    np.testing.assert_allclose(np.asarray(y_sp), np.asarray(y_ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(cache["k"]), np.asarray(cache_ref["k"]),
                               atol=1e-6)


def test_epsum_decode_matches_gathered(rng):
    """moe_epsum_decode_local on a size-1 EP axis == moe_apply_routed."""
    mcfg = MoEConfig(num_experts=8, top_k=2, expert_d_ff=16)
    p = M.init_moe(jax.random.PRNGKey(0), 12, mcfg, "swiglu", jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 12)), jnp.float32)
    logits = M.router_logits(p, x)
    ids, weights, _ = M.topk_route(logits, mcfg)
    y_ref, miss = M.moe_apply_routed(p, x, ids, weights)
    assert not bool(miss.any())
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    fn = shard_map(
        lambda pp, xx, ii, ww: M.moe_epsum_decode_local(
            pp, mcfg, xx, ii, ww, ep_axis="model"),
        mesh=mesh,
        in_specs=({"router": P(None, None),
                   "experts": {kk: P("model", None, None) for kk in p["experts"]}},
                  P("data", None), P("data", None), P("data", None)),
        out_specs=P("data", None),
        check_vma=False,
    )
    y_ep = jax.jit(fn)(p, x, ids, weights)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref), atol=1e-4)
