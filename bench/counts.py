"""Operations and bytes that the served work needs, from shapes alone.

``step_mfu`` and the kernels' roofline shares divide these by the chip's
peaks (``bench/peaks.json``). The counts are of what the algorithm needs,
not of what a program happens to move: per token the routed experts it
actually uses (``top_k`` of them; a prefill chunk reads each distinct
expert once), the attention projections, the KV cache up to the token's
position, the router, the norms, the shared expert and, once per served
token, the lm head. Weights are bfloat16 (the router float32), the KV cache
bfloat16.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

from bench.shapes import Shapes

BF16 = 2
F32 = 4
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PEAKS) -> Dict:
    """The peak FLOP/s and bytes/s of ``device_kind``; unknown kinds raise."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def expected_distinct(experts: int, top_k: int, tokens: int) -> float:
    """Expected number of distinct experts that ``tokens`` tokens route to,
    each choosing ``top_k`` of ``experts`` uniformly."""
    if tokens <= 1:
        return float(min(top_k, experts)) * tokens
    return experts * (1.0 - (1.0 - top_k / experts) ** tokens)


def layer_weight_bytes(s: Shapes) -> float:
    """Bytes of one layer's weights outside the routed experts."""
    d, hd, kvd = s.hidden, s.heads * s.head_dim, s.kv_heads * s.head_dim
    b = (d * hd + 2 * d * kvd + hd * d) * BF16       # q, k, v, o
    b += 2 * d * BF16                                # ln1, ln2
    if s.qk_norm:
        b += 2 * s.head_dim * BF16
    b += d * s.experts * F32                         # router
    if s.shared:
        b += (3 * d * s.shared + d) * BF16           # shared expert + gate
    return float(b)


def layer_token_flops(s: Shapes, context: int) -> float:
    """FLOPs of one token in one layer, attending over ``context`` positions
    (itself included)."""
    d, hd, kvd = s.hidden, s.heads * s.head_dim, s.kv_heads * s.head_dim
    f = 2 * d * (hd + 2 * kvd) + 2 * hd * d          # projections
    f += 4 * context * hd                            # scores and weighted sum
    f += 2 * d * s.experts                           # router
    f += s.top_k * 2 * 3 * d * s.expert_width        # routed experts
    if s.shared:
        f += 2 * 3 * d * s.shared + 2 * d            # shared expert + gate
    f += 2 * 4 * d                                   # two norms
    return float(f)


def head_flops(s: Shapes) -> float:
    return 2.0 * s.hidden * s.vocab + 4.0 * s.hidden


def head_bytes(s: Shapes) -> float:
    return float(s.hidden * s.vocab * BF16 + s.hidden * BF16)


def kv_bytes(s: Shapes, positions: int) -> float:
    """Bytes of K and V for ``positions`` positions of one layer."""
    return float(2 * positions * s.kv_heads * s.head_dim * BF16)


def chunk(s: Shapes, start: int, tokens: int, head: bool) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``tokens`` positions from position ``start`` run
    through every layer in one launch (a decode step is one token), with the
    lm head on the last position if ``head``."""
    ctx_sum = tokens * start + tokens * (tokens + 1) // 2
    flops = s.layers * (tokens * layer_token_flops(s, 0) + 4.0 * ctx_sum
                        * s.heads * s.head_dim)
    routed = expected_distinct(s.experts, s.top_k, tokens) * s.expert_bytes
    byts = s.layers * (layer_weight_bytes(s) + routed
                       + kv_bytes(s, start) + kv_bytes(s, tokens))
    byts += tokens * s.hidden * BF16                 # embedding rows
    if head:
        flops += head_flops(s)
        byts += head_bytes(s)
    return flops, byts


def roofline_s(flops: float, byts: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops_per_s"], byts / peak["hbm_bytes_per_s"])


def topk_gate(tokens: int, experts: int, top_k: int) -> Tuple[float, float]:
    """(operations, bytes) of the fused softmax + top-k gate over a
    [tokens, experts] float32 logit tile: the softmax (max, subtract, exp,
    sum, divide: 5 per logit) and ``top_k`` rounds of max, compare, masked
    index min and mask (4 per logit each); reads the logits, writes ids
    (int32) and weights (float32)."""
    ops = tokens * experts * (5 + 4 * top_k)
    return float(ops), float(tokens * experts * F32 + tokens * top_k * 8)
