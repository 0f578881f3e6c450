"""Plain float32 forward pass of a Qwen-style ``attn_moe`` decoder.

It follows the published modeling code of Qwen3-MoE and Qwen2-MoE (the
family of Qwen1.5-MoE), on the benchmark's own weights (``bench/weights.py``)
and nothing of the program's:

    x   = embed[tokens]
    per layer:
      h   = rmsnorm(x) * ln1
      q, k, v = h @ wq, h @ wk, h @ wv        (Qwen1.5-MoE: + bias, taken as 0)
      q, k = rmsnorm_headdim(q) * q_norm, ... (Qwen3-MoE only)
      q, k = rope(q), rope(k)                 (rotate-half, base rope_theta)
      x   = x + causal_softmax(q k^T / sqrt(dh)) v @ wo   (grouped K/V heads)
      h   = rmsnorm(x) * ln2
      p   = softmax(h @ router); top-k of p; renormalised if norm_topk_prob
      y   = sum_k p_k * down(silu(h @ gate_k) * (h @ up_k))
      y  += sigmoid(h @ shared_gate) * down(silu(h @ sg) * (h @ su))  (shared)
      x   = x + y
    hidden = rmsnorm(x) * final_norm;  logits = hidden @ lm_head

Every matrix product runs at ``Precision.HIGHEST`` in float32, so on a TPU
nothing is rounded to bfloat16. The work is done a layer at a time, with that
layer's experts copied to the device alone and every expert applied to every
token (weighted by zero where it is not routed), so memory stays at one
layer's experts whatever the sequence length.

``quant`` is the control, the precision one step below the configuration's
(``LOWER``): every operand of a weight matrix product is rounded to it before
an otherwise identical product: to bfloat16, or to float8 e4m3 with one scale
per row of activations and per output column of weights.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.shapes import Shapes

HI = jax.lax.Precision.HIGHEST
PAD = 128           # sequences are padded to a multiple of this, so one
                    # compiled program serves many lengths (causal: the pad
                    # never reaches an earlier position)


LOWER = {"bfloat16": "fp8", "float32": "bf16"}   # served type -> control


def _round(x: jax.Array, quant: Optional[str], axis: int) -> jax.Array:
    """``x`` rounded to ``quant`` (float8: one scale per slice along ``axis``)."""
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant == "fp8":
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
        scale = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x


def _mm(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    """x [..., K] @ w [K, N] in float32, operands first rounded to ``quant``."""
    x, w = _round(x, quant, -1), _round(w, quant, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x [S, heads, dh] at positions pos [S], rotate-half convention."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(s: Shapes, x, lw, ew, quant):
    """One decoder layer over one sequence x [S, D] (float32)."""
    f32 = lambda a: a.astype(jnp.float32)
    n = x.shape[0]
    pos = jnp.arange(n)
    h = _rms(x, f32(lw["ln1"]), s.eps)
    q = _mm(h, f32(lw["wq"]), quant).reshape(n, s.heads, s.head_dim)
    k = _mm(h, f32(lw["wk"]), quant).reshape(n, s.kv_heads, s.head_dim)
    v = _mm(h, f32(lw["wv"]), quant).reshape(n, s.kv_heads, s.head_dim)
    if s.qk_norm:
        q = _rms(q, f32(lw["q_norm"]), s.eps)
        k = _rms(k, f32(lw["k_norm"]), s.eps)
    q, k = _rope(q, pos, s.rope_theta), _rope(k, pos, s.rope_theta)
    g = s.heads // s.kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(s.head_dim)
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, precision=HI)
    x = x + _mm(ctx.reshape(n, -1), f32(lw["wo"]), quant)

    h = _rms(x, f32(lw["ln2"]), s.eps)
    probs = jax.nn.softmax(_mm(h, f32(lw["router"]), quant), axis=-1)
    top, ids = jax.lax.top_k(probs, s.top_k)
    if s.norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    comb = jnp.zeros((n, s.experts), jnp.float32)
    comb = comb.at[jnp.arange(n)[:, None], ids].set(top)        # [S, E]

    def expert(y, e):
        wg, wu, wd = (f32(ew[m][e]) for m in ("w_gate", "w_up", "w_down"))
        out = _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)
        return y + comb[:, e][:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(s.experts))
    if s.shared:
        sh = _mm(jax.nn.silu(_mm(h, f32(lw["shared_w_gate"]), quant))
                 * _mm(h, f32(lw["shared_w_up"]), quant),
                 f32(lw["shared_w_down"]), quant)
        y = y + jax.nn.sigmoid(_mm(h, f32(lw["shared_gate"]), quant)) * sh
    return x + y


@functools.partial(jax.jit, static_argnums=(0,))
def _final(s: Shapes, x, norm):
    return _rms(x, norm.astype(jnp.float32), s.eps)


def hidden(s: Shapes, w: Dict, seqs: Sequence[np.ndarray],
           quant: Optional[str] = None) -> List[jax.Array]:
    """Final normed hidden state [len, D] (float32, on the device) of every
    position of each token sequence in ``seqs``."""
    xs, lens = [], []
    for t in seqs:
        t = np.asarray(t, np.int32)
        n = -(-len(t) // PAD) * PAD
        padded = np.zeros(n, np.int32)
        padded[: len(t)] = t
        xs.append(jnp.take(w["embed"], jnp.asarray(padded), axis=0)
                  .astype(jnp.float32))
        lens.append(len(t))
    for layer in range(s.layers):
        lw = {k: v[layer] for k, v in w["layers"].items()}
        ew = {k: jnp.asarray(v[layer, : s.experts]) for k, v in w["experts"].items()}
        for i, x in enumerate(xs):
            xs[i] = _layer(s, x, lw, ew, quant)
        del ew
    return [_final(s, x, w["final_norm"])[:n] for x, n in zip(xs, lens)]


@jax.jit
def _head_stats(hid, head, served):
    """For rows of hid [N, D] and their served tokens [N]: the reference's
    best logit and its logit of the served token."""
    ref = jnp.einsum("nd,dv->nv", hid, head.astype(jnp.float32), precision=HI)
    best = ref.max(axis=-1)
    at = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    return best, at


def served_gaps(s: Shapes, w: Dict, sessions: Sequence[Dict],
                quant: Optional[str] = None) -> np.ndarray:
    """Gap by which each served token's reference logit lies below the
    reference's best, over every served token of every session.

    A session is ``{"prompt": ids, "served": ids}``; served token ``i`` was
    produced from the logits after ``prompt + served[:i]``. With ``quant``
    the served tokens are replaced by those the quantised forward ranks
    first at the same positions (the control: it reads the same prompts and
    tokens, and need not decode)."""
    seqs, rows = [], []
    for ses in sessions:
        p, t = np.asarray(ses["prompt"]), np.asarray(ses["served"])
        seqs.append(np.concatenate([p, t[:-1]]))
        rows.append((len(p) - 1, len(t)))
    ref_h = hidden(s, w, seqs)
    q_h = hidden(s, w, seqs, quant=quant) if quant else None
    head = w["lm_head"]
    gaps = []
    for i, (start, n) in enumerate(rows):
        h = ref_h[i][start : start + n]
        if quant:
            logits = _mm(q_h[i][start : start + n], head.astype(jnp.float32), quant)
            served = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            served = jnp.asarray(np.asarray(sessions[i]["served"], np.int32))
        best, at = _head_stats(h, head, served)
        gaps.append(np.asarray(best - at))
    return np.concatenate(gaps) if gaps else np.zeros(0)
