"""The sizes of a configuration file, read once from its published keys."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict


@dataclass(frozen=True)
class Shapes:
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    experts: int
    top_k: int
    expert_width: int
    shared: int            # shared-expert width (0: none)
    norm_topk_prob: bool
    qk_norm: bool
    rope_theta: float
    eps: float

    @classmethod
    def of(cls, conf: Dict[str, Any]) -> "Shapes":
        heads = conf["num_attention_heads"]
        return cls(
            layers=conf["num_hidden_layers"],
            hidden=conf["hidden_size"],
            heads=heads,
            kv_heads=conf["num_key_value_heads"],
            head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
            vocab=conf["vocab_size"],
            experts=conf["num_experts"],
            top_k=conf["num_experts_per_tok"],
            expert_width=conf["moe_intermediate_size"],
            shared=conf.get("shared_expert_intermediate_size", 0),
            norm_topk_prob=bool(conf["norm_topk_prob"]),
            # Qwen3-MoE attention norms q and k over head_dim; Qwen2-MoE does not
            qk_norm=conf["model_type"] == "qwen3_moe",
            rope_theta=float(conf["rope_theta"]),
            eps=float(conf["rms_norm_eps"]),
        )

    @property
    def expert_bytes(self) -> int:
        """Bytes of one routed expert in bfloat16 (gate, up and down)."""
        return 3 * self.hidden * self.expert_width * 2
