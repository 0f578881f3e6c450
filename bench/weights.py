"""Seeded random weights of an ``attn_moe`` decoder, made by the benchmark.

The same ``--seed`` gives the same weights. They are drawn on the device in
the type they are served in (bfloat16; the router in float32): every weight
outside the experts in one jitted call, the routed experts one layer at a
time by one compiled program, each layer copied into host memory and freed
on the device before the next is drawn. The layout is the benchmark's own,
with a leading layer axis; ``bench/program.py`` hands it to the system under
test and ``bench/reference`` reads it as it is.

Keys (L layers, E experts, D hidden, H/Hkv heads of dh, F expert width,
S shared-expert width, V vocabulary)::

    embed [V, D]  lm_head [D, V]  final_norm [D]
    layers: ln1, ln2 [L, D]  wq [L, D, H*dh]  wk, wv [L, D, Hkv*dh]
            wo [L, H*dh, D]  q_norm, k_norm [L, dh] (qk-norm models)
            router [L, D, E] float32
            shared_w_gate, shared_w_up [L, D, S]  shared_w_down [L, S, D]
            shared_gate [L, D, 1]                 (shared-expert models)
    experts (host numpy): w_gate, w_up [L, R, D, F]  w_down [L, R, F, D]

``R >= E`` expert rows are allocated; rows past ``E`` are zero and never
routed (a program may store its experts padded).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.shapes import Shapes

NORM_JITTER = 0.1   # norm scales are 1 + 0.1 * N(0, 1), so a norm left out shows


def base_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _spec(s: Shapes, bf: str) -> Dict[str, Tuple[Tuple[int, ...], str, str, float]]:
    """name -> (shape, dtype, kind, scale) of every weight outside the experts,
    in the served type ``bf`` (the router in float32)."""
    L, D = s.layers, s.hidden
    f32 = "float32"
    spec = {
        "embed": ((s.vocab, D), bf, "normal", 0.02),
        "lm_head": ((D, s.vocab), bf, "normal", 0.02),
        "final_norm": ((D,), bf, "norm", NORM_JITTER),
        "ln1": ((L, D), bf, "norm", NORM_JITTER),
        "ln2": ((L, D), bf, "norm", NORM_JITTER),
        "wq": ((L, D, s.heads * s.head_dim), bf, "normal", D ** -0.5),
        "wk": ((L, D, s.kv_heads * s.head_dim), bf, "normal", D ** -0.5),
        "wv": ((L, D, s.kv_heads * s.head_dim), bf, "normal", D ** -0.5),
        "wo": ((L, s.heads * s.head_dim, D), bf, "normal",
               (s.heads * s.head_dim) ** -0.5),
        "router": ((L, D, s.experts), f32, "normal", D ** -0.5),
    }
    if s.qk_norm:
        spec["q_norm"] = ((L, s.head_dim), bf, "norm", NORM_JITTER)
        spec["k_norm"] = ((L, s.head_dim), bf, "norm", NORM_JITTER)
    if s.shared:
        spec["shared_w_gate"] = ((L, D, s.shared), bf, "normal", D ** -0.5)
        spec["shared_w_up"] = ((L, D, s.shared), bf, "normal", D ** -0.5)
        spec["shared_w_down"] = ((L, s.shared, D), bf, "normal", s.shared ** -0.5)
        spec["shared_gate"] = ((L, D, 1), bf, "normal", D ** -0.5)
    return spec


def _draw(key, shape, dtype, kind, scale):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        z = 1.0 + scale * z
    else:
        z = scale * z
    return z.astype(dtype)


@functools.partial(jax.jit, static_argnums=(1,))
def _dense(key, spec_items):
    out = {}
    for i, (name, (shape, dtype, kind, scale)) in enumerate(spec_items):
        out[name] = _draw(jax.random.fold_in(key, i), shape, dtype, kind, scale)
    return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _expert_layer(key, layer, rows, experts, hidden, width, dtype):
    """One layer's expert rows [R, ...]; rows past ``experts`` are zero."""
    k = jax.random.fold_in(key, layer)
    live = (jnp.arange(rows) < experts)[:, None, None]
    out = {}
    for i, (name, shape, fan) in enumerate((
        ("w_gate", (rows, hidden, width), hidden),
        ("w_up", (rows, hidden, width), hidden),
        ("w_down", (rows, width, hidden), width),
    )):
        w = jax.random.normal(jax.random.fold_in(k, i), shape, jnp.float32)
        out[name] = jnp.where(live, w * fan ** -0.5, 0.0).astype(dtype)
    return out


def make_weights(s: Shapes, seed: int, rows: int = 0,
                 dtype: str = "bfloat16") -> Dict:
    """Weights of the model ``s`` from ``seed`` in the served ``dtype``:
    device arrays, plus the experts as host numpy arrays under
    ``"experts"``."""
    rows = max(rows, s.experts)
    key = base_key(seed)
    dense = _dense(jax.random.fold_in(key, 0),
                   tuple(sorted(_spec(s, dtype).items())))
    ekey = jax.random.fold_in(key, 1)
    experts: Dict[str, np.ndarray] = {}
    for layer in range(s.layers):
        w = _expert_layer(ekey, layer, rows, s.experts, s.hidden,
                          s.expert_width, dtype)
        for name, arr in w.items():
            if name not in experts:
                experts[name] = np.empty((s.layers,) + arr.shape, arr.dtype)
            experts[name][layer] = np.asarray(arr)
            arr.delete()
    out = {k: dense[k] for k in ("embed", "lm_head", "final_norm")}
    out["layers"] = {k: v for k, v in dense.items() if k not in out}
    out["experts"] = experts
    return out
