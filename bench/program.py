"""The one file that knows the system under test's interface.

It builds the program's model config from a configuration file, hands the
benchmark's weights over in the program's parameter layout, and builds the
engine through the program's own launcher (``repro.launch.serve``): the
rotary engine, greedy, batch 1.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

from bench.shapes import Shapes


def model_config(conf: Dict[str, Any]):
    """The registry's config for ``conf["program"]["arch"]`` with its depth
    and sizes set from the configuration file."""
    from repro.config import get_config
    from repro.configs import cut_depth

    s = Shapes.of(conf)
    cfg = get_config(conf["program"]["arch"])
    full = cfg.num_layers
    if s.layers < full:
        cfg = cut_depth(cfg, s.layers)
    m = cfg.moe
    shared = (m.num_shared_experts, m.shared_d_ff)
    if m.num_shared_experts * m.shared_d_ff != s.shared:
        shared = (1, s.shared) if s.shared else (0, 0)
    moe = dataclasses.replace(
        m, num_experts=s.experts, top_k=s.top_k, expert_d_ff=s.expert_width,
        num_shared_experts=shared[0], shared_d_ff=shared[1],
        norm_topk_prob=s.norm_topk_prob,
        padded_experts=m.padded_experts if m.padded_experts >= s.experts else 0,
    )
    attn = dataclasses.replace(
        cfg.attention, num_heads=s.heads, num_kv_heads=s.kv_heads,
        head_dim=s.head_dim, qk_norm=s.qk_norm, rope_theta=s.rope_theta,
    )
    cfg = dataclasses.replace(
        cfg, d_model=s.hidden, vocab_size=s.vocab, attention=attn, moe=moe,
        dtype=conf["torch_dtype"], tie_embeddings=conf["tie_word_embeddings"],
    )
    if (cfg.num_layers, cfg.has_moe, cfg.mlp, cfg.norm) != (
            s.layers, True, "swiglu", "rmsnorm"):
        raise ValueError(f"{cfg.name} is not the decoder the reference follows")
    return cfg


def expert_rows(cfg) -> int:
    """Expert rows the program stores per layer (padding included)."""
    return cfg.moe.storage_experts


def params(cfg, w: Dict) -> Dict:
    """The benchmark's weights in the program's parameter tree (as
    ``repro.models.init_params(experts_on_host=True)`` lays it out)."""
    lw = w["layers"]
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo")}
    if cfg.attention.qk_norm:
        attn["q_norm"], attn["k_norm"] = lw["q_norm"], lw["k_norm"]
    moe = {"router": lw["router"], "experts": dict(w["experts"])}
    if cfg.moe.num_shared_experts:
        moe["shared"] = {k: lw["shared_" + k] for k in ("w_gate", "w_up", "w_down")}
        moe["shared_gate"] = lw["shared_gate"]
    block = {"ln1": {"scale": lw["ln1"]}, "attn": attn,
             "ln2": {"scale": lw["ln2"]}, "moe": moe}
    return {
        "embed": w["embed"],
        "segments": ((block,),),
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": w["lm_head"],
    }


def build_engine(cfg, prm: Dict, cell: Dict, seed: int, tracer=None):
    """The rotary engine the cell describes, through the program's launcher."""
    from repro.launch import serve

    argv = ["--arch", cfg.name, "--size", "full", "--engine", "rotary",
            "--residency", cell["residency"], "--slots", str(cell["slots"]),
            "--cache-len", str(cell["cache_len"]),
            "--prefill-chunk", str(cell["prefill_chunk"]),
            "--seed", str(seed % 2**31)]
    args = serve.build_parser().parse_args(argv)
    return serve.build_engine(args, cfg, prm, tracer)
