"""jit'd dispatch wrappers around the Pallas kernels.

On the CPU backend (the test suite) kernels run in ``interpret=True`` (the
kernel body executes in Python — correctness only); on a TPU backend the same
calls lower through Mosaic. Any other backend is an error rather than a
silent interpreter run. Callers use these wrappers, never the kernels
directly, so the backend switch is one place.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import moe_gmm as _gmm
from repro.kernels import topk_gate as _tk


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels lower for TPU (or interpret on CPU), not {backend!r}"
        )
    return backend == "cpu"


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    soft_cap: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 128,
) -> jax.Array:
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, soft_cap=soft_cap,
        block_q=block_q, block_kv=block_kv, interpret=_interpret(),
    )


def decode_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    cur_len: jax.Array,
    window: Optional[int] = None,
    soft_cap: Optional[float] = None,
    block_kv: int = 256,
) -> jax.Array:
    """Adapter for the model's decode path: q [B,1,H,dh], cache k/v [B,S,Hkv,dh].

    ``cur_len`` (scalar or per-row [B]) is the number of tokens BEFORE this one;
    the new token was already written, so valid length is cur_len+1. Sliding
    windows fall back to the jnp path in the caller (ring-position masking is
    cache-layout specific).
    """
    b = q.shape[0]
    lengths = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,)) + 1
    out = _dec.decode_attention(
        q[:, 0], k, v, lengths, soft_cap=soft_cap,
        block_kv=block_kv, interpret=_interpret(),
    )
    return out[:, None]                                    # [B,1,H,dh]


def moe_slot_ffn(
    x: jax.Array, slots: dict, group_sizes: jax.Array, group_planes: jax.Array
) -> jax.Array:
    """Routed-expert FFN for the compiled engine paths (traceable): rows
    sorted by group, each group times its plane of ``slots`` (bf16/f32).

    On TPU this lowers three ``slot_gmm`` kernels, which read each used plane
    in place; on CPU the same grouping runs through ``jax.lax.ragged_dot``
    over the groups' planes, since an interpreted kernel inside a jitted hot
    loop is pure overhead.
    """
    if not _interpret():
        return _gmm.moe_slot_ffn(x, slots, group_sizes, group_planes)

    def matmul(rows, name):
        w = jnp.take(slots[name], group_planes, axis=0)
        return jax.lax.ragged_dot(
            rows, w, group_sizes, preferred_element_type=jnp.float32
        ).astype(rows.dtype)

    return _gmm.expert_ffn(x, slots, matmul)


def slot_gmm(
    x: jax.Array, w: jax.Array, group_sizes: jax.Array, group_planes: jax.Array,
    scale: Optional[jax.Array] = None, mn: Optional[jax.Array] = None, **blocks
) -> jax.Array:
    return _gmm.slot_gmm(x, w, group_sizes, group_planes, scale, mn,
                         interpret=_interpret(), **blocks)


def topk_gate(logits: jax.Array, k: int, *, normalize: bool = True
              ) -> Tuple[jax.Array, jax.Array]:
    return _tk.topk_gate(logits, k, normalize=normalize, interpret=_interpret())


def route_topk(
    logits: jax.Array,              # [T, E]
    k: int,
    *,
    normalize: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Router gate for the compiled decode/prefill paths (traceable).

    On TPU this lowers the fused Pallas ``topk_gate`` (one VMEM pass, no full
    sort); on CPU it runs ``jax.lax.top_k`` over a softmax, since an
    interpreted kernel inside a jitted hot loop is pure overhead. Both break
    ties lowest-index-first, so routing is backend-independent.
    """
    if not _interpret():
        t, e = logits.shape
        bt = min(256, t)
        pad = (-t) % bt
        if pad:
            logits = jnp.concatenate(
                [logits, jnp.full((pad, e), _tk.NEG_INF, logits.dtype)], axis=0
            )
        ids, w = _tk.topk_gate(logits, k, normalize=normalize)
        return ids[:t], w[:t]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, ids = jax.lax.top_k(probs, k)
    if normalize:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return ids.astype(jnp.int32), w
