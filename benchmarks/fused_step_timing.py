"""Device time of the fused steps alone, on a TPU.

Builds qwen2-moe-a2.7b cut to 4 layers at published widths (bf16, 60
experts top-4 of width 1408, stacked slot planes [4, 61, 2048, 1408] with
every expert resident, as the rotary engine runs the benchmark's chat cell)
and times, in a loop with ``block_until_ready``:

- ``chunk``: the 16-token prefill chunk (no head, no demand);
- ``decode``: the one-token decode step;
- ``ffn``: the routed-expert FFN of one chunk layer alone (64 picks grouped
  by slot through ``ops.moe_slot_ffn``), where the tree has it;

and the chunk at other weight tiles of ``slot_gmm`` (``--tiles``). Prints one
JSON line per reading (median and quartiles of ``--calls`` calls, ms).
Works on a tree without the grouped path too (it then times the gather)::

    PYTHONPATH=src python benchmarks/fused_step_timing.py --calls 50
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import get_config
from repro.configs import cut_depth
from repro.core.engine import build_fused_decode_step, build_fused_prefill_step
from repro.kernels import moe_gmm, ops
from repro.models import init_params
from repro.models import moe as moe_mod
from repro.models.transformer import Runtime, zero_state

CHUNK = 16


def _timed(fn, calls: int):
    fn()                                            # compile + warm
    fn()
    ms = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ms.append((time.perf_counter() - t0) * 1e3)
    q1, med, q3 = statistics.quantiles(ms, n=4)
    return {"median_ms": med, "q1_ms": q1, "q3_ms": q3, "calls": calls}


def _model(seed: int):
    cfg = cut_depth(get_config("qwen2-moe-a2.7b"), 4)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    blk = shapes["segments"][0][0]
    blk = {**blk, "moe": {k: v for k, v in blk["moe"].items() if k != "experts"}}
    shapes = {**shapes, "segments": ((blk,),)}
    leaves, tree = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves) + 3)
    params = tree.unflatten([
        (0.02 * jax.random.normal(k, a.shape, jnp.float32)).astype(a.dtype)
        for k, a in zip(keys, leaves)
    ])
    layers, slots = cfg.num_layers, cfg.moe.num_experts + 1
    d, f = cfg.d_model, cfg.moe.expert_d_ff
    shape = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    planes = {
        n: (0.02 * jax.random.normal(k, (layers, slots) + s, jnp.float32)
            ).astype(jnp.bfloat16).at[:, -1].set(0)
        for k, (n, s) in zip(keys[-3:], shape.items())
    }
    lut = np.full((layers, cfg.moe.storage_experts), slots - 1, np.int32)
    lut[:, : slots - 1] = np.arange(slots - 1)
    residency = ({"slots": planes, "lut": jnp.asarray(lut)},)
    return cfg, params, residency


def _steps(cfg, params, residency, rt, calls: int, with_decode: bool):
    out = {}
    prefill = build_fused_prefill_step(cfg, rt, with_demand=False, with_head=False)
    tokens = jnp.arange(CHUNK, dtype=jnp.int32)[None] * 7919 % cfg.vocab_size
    box = {"state": zero_state(cfg, 1, rt.cache_len)}

    def chunk():
        _, box["state"], _ = prefill(params, None, tokens, box["state"],
                                     jnp.int32(0), residency)
        return box["state"]

    out["chunk"] = _timed(chunk, calls)
    if with_decode:
        step = build_fused_decode_step(cfg, rt, with_demand=False)
        token = jnp.array([11], jnp.int32)

        def decode():
            logits, box["state"], _ = step(params, None, token, box["state"],
                                           jnp.int32(CHUNK), residency)
            return logits

        out["decode"] = _timed(decode, calls)
    return out


def _ffn(cfg, residency, calls: int, seed: int):
    """One chunk layer's routed FFN alone: 64 picks, grouped by slot."""
    planes = residency[0]["slots"]
    slots = planes["w_up"].shape[1]
    rng = np.random.default_rng(seed)
    picks = np.stack([rng.choice(slots - 1, cfg.moe.top_k, replace=False)
                      for _ in range(CHUNK)]).reshape(-1)
    sizes = jnp.asarray(np.bincount(np.sort(picks), minlength=slots - 1), jnp.int32)
    group_planes = jnp.arange(slots - 1, dtype=jnp.int32) + slots   # layer 1
    flat = {n: v.reshape((-1,) + v.shape[-2:]) for n, v in planes.items()}
    x = (0.02 * jax.random.normal(jax.random.PRNGKey(seed), (picks.size, cfg.d_model))
         ).astype(jnp.bfloat16)
    fn = jax.jit(ops.moe_slot_ffn)
    reading = _timed(lambda: fn(x, flat, sizes, group_planes), calls)
    reading["groups"] = int(np.unique(picks).size)
    return reading


def _require_tpu() -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit("fused_step_timing.py times the chip: no TPU backend")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiles", type=int, nargs="*", default=[],
                    help="slot_gmm weight tiles to time the chunk at besides the default")
    ap.add_argument("--label", default="")
    a = ap.parse_args()
    _require_tpu()
    cfg, params, residency = _model(a.seed)
    rt = Runtime(cache_len=1024)
    grouped = hasattr(moe_mod, "_grouped_slot_ffn")

    def emit(name, reading, **extra):
        print(json.dumps({"label": a.label, "reading": name, **reading, **extra}),
              flush=True)

    for name, reading in _steps(cfg, params, residency, rt, a.calls, True).items():
        emit(name, reading, weight_block=getattr(moe_gmm, "_WEIGHT_BLOCK", None))
    if not grouped:
        return
    emit("ffn", _ffn(cfg, residency, a.calls, a.seed), weight_block=moe_gmm._WEIGHT_BLOCK)
    default = moe_gmm._WEIGHT_BLOCK
    for tile in a.tiles:
        moe_gmm._WEIGHT_BLOCK = tile
        jax.clear_caches()
        emit("chunk", _steps(cfg, params, residency, rt, a.calls, False)["chunk"],
             weight_block=tile)
        emit("ffn", _ffn(cfg, residency, a.calls, a.seed), weight_block=tile)
    moe_gmm._WEIGHT_BLOCK = default


if __name__ == "__main__":
    main()
