"""Tiny versions of the benchmark's configurations and cells for CPU tests:
the published files with every size shrunk, written under a temporary root
that the harness searches before ``bench/``."""
import json
import os

import benchpath  # noqa: F401

from bench import harness

SIZES = {
    "qwen3": dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                  head_dim=16, num_experts=8, num_experts_per_tok=2,
                  moe_intermediate_size=32, vocab_size=256, num_hidden_layers=2),
    "qwen15": dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
                   num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
                   shared_expert_intermediate_size=48, vocab_size=256,
                   num_hidden_layers=2),
}
SOURCE = {"qwen3": "qwen3-30b-a3b-4L", "qwen15": "qwen1.5-moe-a2.7b-4L"}


def conf(family: str, dtype: str = "bfloat16") -> dict:
    c = harness.load_json("configs", SOURCE[family])
    c.update(SIZES[family], torch_dtype=dtype)
    return c


def cell(slots: int, limit: float = 0.05) -> dict:
    return {"residency": "rotary", "slots": slots,
            "prefill_chunk": 8, "cache_len": 64,
            "limits": {"widest_gap": limit}}


def traffic(decode: bool) -> dict:
    return {"prompt_len": 16, "output_len": 12 if decode else 6}


def write_cell(root: str, family: str, slots: int, decode: bool,
               dtype: str = "bfloat16", limit: float = 0.05) -> dict:
    """Files of one tiny cell under ``root``; returns its workload entry."""
    name = f"tiny-{family}-{slots}-{'decode' if decode else 'chat'}"
    for kind, body in (("configs", conf(family, dtype)), ("cells", cell(slots, limit)),
                       ("traffic", traffic(decode))):
        os.makedirs(os.path.join(root, kind), exist_ok=True)
        with open(os.path.join(root, kind, name + ".json"), "w") as f:
            json.dump(body, f)
    return {"name": name, "config": name, "traffic": name, "chips": 1}
