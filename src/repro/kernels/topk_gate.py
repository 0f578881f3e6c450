"""Fused softmax + top-k router gate.

One VMEM pass over a [bt, E] logit tile produces ids + normalized weights:
softmax, then k iterations of (argmax, mask) — k is static and small, the loop
unrolls into VPU max-reductions, avoiding a full sort and a second HBM pass
over probabilities. Matches ``jax.lax.top_k`` on ties by lowest-index-wins.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _topk_kernel(x_ref, ids_ref, w_ref, *, k: int, normalize: bool):
    logits = x_ref[...].astype(jnp.float32)                 # [bt, E]
    bt, e = logits.shape
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    probs = p / p.sum(axis=-1, keepdims=True)

    work = probs
    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, e), 1)
    ids = []
    ws = []
    for _ in range(k):
        w = work.max(axis=-1)
        # lowest index among maxima (matches lax.top_k tie-breaking)
        is_max = work >= w[:, None]
        idx = jnp.min(jnp.where(is_max, cols, e), axis=-1)
        ids.append(idx)
        ws.append(w)
        work = jnp.where(cols == idx[:, None], -1.0, work)
    ids_arr = jnp.stack(ids, axis=-1).astype(jnp.int32)     # [bt, k]
    w_arr = jnp.stack(ws, axis=-1)
    if normalize:
        w_arr = w_arr / jnp.maximum(w_arr.sum(-1, keepdims=True), 1e-9)
    ids_ref[...] = ids_arr
    w_ref[...] = w_arr


@functools.partial(
    jax.jit, static_argnames=("k", "normalize", "block_t", "interpret")
)
def topk_gate(
    logits: jax.Array,              # [T, E]
    k: int,
    *,
    normalize: bool = True,
    block_t: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    t, e = logits.shape
    bt = min(block_t, t)
    assert t % bt == 0, f"T={t} must divide block_t={bt}"
    kernel = functools.partial(_topk_kernel, k=k, normalize=normalize)
    ids, w = pl.pallas_call(
        kernel,
        grid=(t // bt,),
        in_specs=[pl.BlockSpec((bt, e), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, k), jnp.int32),
            jax.ShapeDtypeStruct((t, k), jnp.float32),
        ],
        interpret=interpret,
        name="topk_gate",
    )(logits)
    return ids, w
