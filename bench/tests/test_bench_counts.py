"""Operations and bytes at published widths, and the peaks table."""
import json

import pytest

import benchpath  # noqa: F401

from bench import counts, harness
from bench.shapes import Shapes


def shapes(name):
    return Shapes.of(harness.load_json("configs", name))


def test_qwen3_routed_expert_bytes_per_token_per_layer():
    s = shapes("qwen3-30b-a3b-4L")
    assert s.expert_bytes == 3 * 2048 * 768 * 2 == 9_437_184        # 9.44 MB
    routed = counts.expected_distinct(s.experts, s.top_k, 1) * s.expert_bytes
    assert routed == 8 * 9_437_184                                    # 75.5 MB
    # one decode step at position 0 of one layer, less its other weights
    _, byts = counts.chunk(s, 0, 1, head=False)
    per_layer = (byts - s.hidden * 2) / s.layers
    other = counts.layer_weight_bytes(s) + counts.kv_bytes(s, 1)
    assert per_layer - other == pytest.approx(75.497472e6, rel=1e-6)


def test_qwen15_shapes_and_shared_expert():
    s = shapes("qwen1.5-moe-a2.7b-4L")
    assert (s.heads, s.kv_heads, s.head_dim, s.shared) == (16, 16, 128, 5632)
    assert s.expert_bytes == 3 * 2048 * 1408 * 2                      # 17.3 MB
    attn = 4 * 2048 * 2048 * 2
    shared = (3 * 2048 * 5632 + 2048) * 2
    norms, router = 2 * 2048 * 2, 2048 * 60 * 4
    assert counts.layer_weight_bytes(s) == attn + shared + norms + router


def test_chunk_reads_each_distinct_expert_once():
    s = shapes("qwen1.5-moe-a2.7b-4L")
    n16 = counts.expected_distinct(60, 4, 16)
    assert 4 < n16 < 16 * 4 and n16 <= 60
    assert counts.expected_distinct(60, 4, 10_000) == pytest.approx(60)
    f1, b1 = counts.chunk(s, 0, 16, head=True)
    f2, b2 = counts.chunk(s, 0, 16, head=False)
    assert f1 - f2 == counts.head_flops(s) and b1 - b2 == counts.head_bytes(s)


def test_roofline_takes_the_larger_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_s(1000, 10, peak) == 10.0
    assert counts.roofline_s(10, 1000, peak) == 100.0


def test_topk_gate_counts():
    ops, byts = counts.topk_gate(16, 60, 4)
    assert ops == 16 * 60 * (5 + 16) and byts == 16 * 60 * 4 + 16 * 4 * 8


def test_peaks_of_v5e_and_unknown_kind_raises(tmp_path):
    p = counts.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("cpu")
    other = tmp_path / "peaks.json"
    other.write_text(json.dumps({"source": "test", "devices": {}}))
    with pytest.raises(KeyError):
        counts.peaks("TPU v5 lite", str(other))
