"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests).

No pallas imports here: these are the semantics, written for clarity not speed.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.attention import reference_attention


def slot_gmm_ref(
    x: jax.Array,              # [M, K] rows sorted by group
    w: jax.Array,              # [P, K, N] slot planes ([P, K/2, N] u8 if int4)
    group_sizes: jax.Array,    # [G] int32 rows per group
    group_planes: jax.Array,   # [G] int32 plane each group multiplies
    scale: Optional[jax.Array] = None,   # int8: [P, N] f32 | int4: [P, K/G, N] f16
    mn: Optional[jax.Array] = None,      # int4: [P, K/G, N] f16 group mins
) -> jax.Array:
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(x.shape[0])
    group = jnp.minimum(jnp.searchsorted(ends, row, side="right"),
                        group_sizes.shape[0] - 1)
    plane = group_planes[group]                                   # [M]
    live = (row < ends[-1])[:, None]
    quantized = w.dtype in (jnp.int8, jnp.uint8)
    if w.dtype == jnp.uint8:             # grouped int4: dequant BEFORE the dot
        from repro.quant import dequantize_int4

        w = dequantize_int4(w, scale, mn)
    wr = jnp.take(w, plane, axis=0).astype(jnp.float32)          # [M, K, N]
    out = jnp.einsum("mk,mkn->mn", x.astype(jnp.float32), wr)
    if w.dtype == jnp.int8:
        out = out * jnp.take(scale, plane, axis=0)
    return jnp.where(live, out, 0).astype(jnp.float32 if quantized else x.dtype)


def moe_slot_ffn_ref(
    x: jax.Array,              # [M, D] rows sorted by group
    slots: dict,               # w_gate/w_up/w_down (+ scale_* / min_* when quantized)
    group_sizes: jax.Array,
    group_planes: jax.Array,
) -> jax.Array:
    def g(name, xx=x):
        return slot_gmm_ref(
            xx, slots[name], group_sizes, group_planes,
            slots.get(f"scale_{name}"), slots.get(f"min_{name}"),
        )

    if "w_gate" in slots:
        h = jax.nn.silu(g("w_gate")) * g("w_up")
    else:
        h = jax.nn.gelu(g("w_up"))
    return g("w_down", h.astype(jnp.float32))


def flash_attention_ref(
    q: jax.Array,              # [B, Sq, H, dh]
    k: jax.Array,              # [B, Skv, Hkv, dh]
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    soft_cap: Optional[float] = None,
) -> jax.Array:
    return reference_attention(q, k, v, causal=causal, window=window, soft_cap=soft_cap)


def decode_attention_ref(
    q: jax.Array,              # [B, H, dh]
    k: jax.Array,              # [B, S, Hkv, dh]
    v: jax.Array,
    lengths: jax.Array,        # [B] int32: valid cache positions per batch row
    *,
    soft_cap: Optional[float] = None,
) -> jax.Array:
    b, h, dh = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, dh).astype(jnp.float32)
    logits = jnp.einsum("bhgd,bshd->bhgs", qg, k.astype(jnp.float32)) / jnp.sqrt(dh)
    if soft_cap is not None:
        logits = soft_cap * jnp.tanh(logits / soft_cap)
    valid = jnp.arange(s)[None, :] < lengths[:, None]            # [B, S]
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, h, dh).astype(q.dtype)


def topk_gate_ref(logits: jax.Array, k: int, *, normalize: bool = True
                  ) -> Tuple[jax.Array, jax.Array]:
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    if normalize:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return ids.astype(jnp.int32), weights
