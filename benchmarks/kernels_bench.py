"""Kernel micro-benchmarks (beyond paper): Pallas kernels vs pure-jnp references.

On this CPU container the kernels run in interpret mode, so wall-times compare
the REFERENCE implementations while the kernels are validated for correctness;
the roofline placement column reports the kernel's arithmetic intensity and the
v5e-bound term that dominates at the given shape.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

PEAK = 197e12
HBM = 819e9


def _time(fn, *args, reps=3) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def run() -> List[Dict]:
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    rows = []

    # slot-LUT grouped matmul
    e, c, d, f, s = 8, 64, 256, 512, 6
    x = jnp.asarray(rng.standard_normal((e, c, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((s + 1, d, f)), jnp.float32)
    lut = jnp.asarray(rng.integers(0, s + 1, e), jnp.int32)
    jit_ref = jax.jit(lambda x, w, l: ref.slot_gmm_ref(x, w, l))
    t_ref = _time(jit_ref, x, w, lut)
    out_k = ops.slot_gmm(x, w, lut, block_c=64, block_f=128, block_d=128)
    err = float(jnp.abs(out_k - jit_ref(x, w, lut)).max())
    flops = 2 * e * c * d * f
    bytes_ = (e * c * d + e * c * f) * 4 + (s + 1) * d * f * 4
    ai = flops / bytes_
    rows.append({
        "kernel": "slot_gmm", "ref_us": round(t_ref * 1e6, 1),
        "allclose_err": err, "arith_intensity": round(ai, 1),
        "v5e_bound": "compute" if ai > PEAK / HBM else "memory",
    })

    # flash attention
    b, sq, h, hkv, dh = 1, 512, 8, 2, 64
    q = jnp.asarray(rng.standard_normal((b, sq, h, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, sq, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, sq, hkv, dh)), jnp.float32)
    jit_ref2 = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
    t_ref2 = _time(jit_ref2, q, k, v)
    out_k2 = ops.flash_attention(q, k, v, block_q=128, block_kv=128)
    err2 = float(jnp.abs(out_k2 - jit_ref2(q, k, v)).max())
    flops = 4 * b * h * sq * sq * dh / 2
    bytes_ = (b * sq * (h + 2 * hkv) * dh * 2) * 4
    rows.append({
        "kernel": "flash_attention", "ref_us": round(t_ref2 * 1e6, 1),
        "allclose_err": err2, "arith_intensity": round(flops / bytes_, 1),
        "v5e_bound": "compute" if flops / bytes_ > PEAK / HBM else "memory",
    })

    # decode attention
    b2, s2 = 8, 4096
    qd = jnp.asarray(rng.standard_normal((b2, h, dh)), jnp.float32)
    kd = jnp.asarray(rng.standard_normal((b2, s2, hkv, dh)), jnp.float32)
    vd = jnp.asarray(rng.standard_normal((b2, s2, hkv, dh)), jnp.float32)
    lengths = jnp.full((b2,), s2, jnp.int32)
    jit_ref3 = jax.jit(lambda q, k, v, l: ref.decode_attention_ref(q, k, v, l))
    t_ref3 = _time(jit_ref3, qd, kd, vd, lengths)
    from repro.kernels.decode_attention import decode_attention

    out_k3 = decode_attention(qd, kd, vd, lengths, block_kv=512, interpret=True)
    err3 = float(jnp.abs(out_k3 - jit_ref3(qd, kd, vd, lengths)).max())
    flops = 4 * b2 * h * s2 * dh
    bytes_ = 2 * b2 * s2 * hkv * dh * 4
    rows.append({
        "kernel": "decode_attention", "ref_us": round(t_ref3 * 1e6, 1),
        "allclose_err": err3, "arith_intensity": round(flops / bytes_, 2),
        "v5e_bound": "memory (KV stream)",
    })

    # topk gate (prefill shape)
    t4, e4, k4 = 4096, 128, 8
    logits = jnp.asarray(rng.standard_normal((t4, e4)), jnp.float32)
    jit_ref4 = jax.jit(lambda l: ref.topk_gate_ref(l, k4))
    t_ref4 = _time(jit_ref4, logits)
    ids_k, w_k = ops.topk_gate(logits, k4)
    ids_r, w_r = jit_ref4(logits)
    rows.append({
        "kernel": "topk_gate", "ref_us": round(t_ref4 * 1e6, 1),
        "allclose_err": float(jnp.abs(w_k - w_r).max()) + float((ids_k != ids_r).sum()),
        "arith_intensity": 0.1, "v5e_bound": "memory (one pass)",
    })

    # topk gate at DECODE shapes (the RotaryEngine hot path routes [B, E]
    # per MoE layer per token) + the backend-dispatching route_topk wrapper
    from repro.kernels.ops import route_topk

    for tb in (1, 2, 8):
        logits_d = jnp.asarray(rng.standard_normal((tb, e4)), jnp.float32)
        jit_refd = jax.jit(lambda l: ref.topk_gate_ref(l, k4))
        t_refd = _time(jit_refd, logits_d)
        ids_k, w_k = ops.topk_gate(logits_d, k4)
        ids_a, w_a = jax.jit(lambda l: route_topk(l, k4))(logits_d)
        ids_r, w_r = jit_refd(logits_d)
        err = (
            float(jnp.abs(w_k - w_r).max()) + float((ids_k != ids_r).sum())
            + float(jnp.abs(w_a - w_r).max()) + float((ids_a != ids_r).sum())
        )
        rows.append({
            "kernel": f"topk_gate_decode_b{tb}", "ref_us": round(t_refd * 1e6, 1),
            "allclose_err": err,
            "arith_intensity": 0.1, "v5e_bound": "memory (one pass)",
        })
    return rows


def main() -> None:
    rows = run()
    for r in rows:
        print(f"  {r['kernel']:18s} ref={r['ref_us']:>9}us err={r['allclose_err']:.2e} "
              f"AI={r['arith_intensity']} bound={r['v5e_bound']}")
        assert r["allclose_err"] < 1e-2
    print("kernels_bench,all_validated,1")


if __name__ == "__main__":
    main()
