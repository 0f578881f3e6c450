"""Compile-only rehearsals of the main-path Pallas kernels and fused steps
for a TPU v5e.

The TPU compiler is installed with jaxlib, and it compiles for a chip that is
described rather than attached: these tests lower each kernel at the widths
of the models the repo serves and hand it to that compiler, so a block shape
Mosaic refuses fails here instead of on the chip. The fused model steps are
lowered the same way, and their compiled HLO is checked for the copies the
TPU compiler would make. Nothing runs; a pass says nothing about results or
speed.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every test worker
imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.configs import cut_depth
from repro.core.engine import build_fused_decode_step, build_fused_prefill_step
from repro.kernels.decode_attention import decode_attention
from repro.kernels.moe_gmm import slot_gmm
from repro.kernels.topk_gate import topk_gate
from repro.models import init_params
from repro.models.transformer import Runtime, zero_state


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    can never be read back without the chip; keep this file silent."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("t,e,k", [
    (1, 128, 8),        # qwen36-35b-a3b decode, batch 1
    (256, 128, 8),      # qwen36-35b-a3b prefill chunk
    (1, 60, 4),         # qwen2-moe-a2.7b decode
    (256, 60, 4),       # qwen2-moe-a2.7b prefill chunk
])
def test_topk_gate_compiles_for_v5e(one_chip, no_compile_cache, t, e, k):
    _compile(
        lambda x: topk_gate(x, k),
        _spec(one_chip, (t, e), jnp.float32),
    )


@pytest.mark.parametrize("b", [1, 4])
def test_decode_attention_compiles_for_v5e(one_chip, no_compile_cache, b):
    # qwen36-35b-a3b: 32 query / 4 KV heads of 128, bf16 cache of 4096
    h, hkv, dh, s = 32, 4, 128, 4096
    _compile(
        lambda q, k, v, n: decode_attention(q, k, v, n),
        _spec(one_chip, (b, h, dh), jnp.bfloat16),
        _spec(one_chip, (b, s, hkv, dh), jnp.bfloat16),
        _spec(one_chip, (b, s, hkv, dh), jnp.bfloat16),
        _spec(one_chip, (b,), jnp.int32),
    )


@pytest.mark.parametrize("quant", [None, "int8", "int4"])
@pytest.mark.parametrize("name", ["w_up", "w_down"])
def test_slot_gmm_compiles_for_v5e(one_chip, no_compile_cache, quant, name):
    # qwen36-35b-a3b experts: E=128 routed through 32 slots (+1 miss slot),
    # D=2048, F=768; w_down contracts over F instead of D
    e, slots, c, group = 128, 33, 128, 64
    d, f = (2048, 768) if name == "w_up" else (768, 2048)
    x = _spec(one_chip, (e, c, d), jnp.bfloat16)
    lut = _spec(one_chip, (e,), jnp.int32)
    if quant is None:
        w = _spec(one_chip, (slots, d, f), jnp.bfloat16)
        _compile(lambda x, w, lut: slot_gmm(x, w, lut), x, w, lut)
    elif quant == "int8":
        w = _spec(one_chip, (slots, d, f), jnp.int8)
        sc = _spec(one_chip, (slots, f), jnp.float32)
        _compile(lambda x, w, lut, sc: slot_gmm(x, w, lut, sc), x, w, lut, sc)
    else:
        w = _spec(one_chip, (slots, d // 2, f), jnp.uint8)
        sc = _spec(one_chip, (slots, d // group, f), jnp.float16)
        mn = _spec(one_chip, (slots, d // group, f), jnp.float16)
        _compile(
            lambda x, w, lut, sc, mn: slot_gmm(x, w, lut, sc, mn),
            x, w, lut, sc, mn,
        )


# qwen2-moe-a2.7b experts: 60 slots + the miss slot per layer, D=2048, F=1408
_SLOTS, _D, _F = 61, 2048, 1408
_PLANE_BYTES = _SLOTS * _D * _F * 2


@pytest.fixture(scope="module")
def fused_qwen2_moe(one_chip, no_compile_cache):
    """The fused decode step and 16-token prefill chunk of qwen2-moe-a2.7b
    cut to 4 layers at published widths, compiled over stacked slot planes
    [4, 61, ...] with the routed experts out of the parameters, as the rotary
    engine runs them."""
    cfg = cut_depth(get_config("qwen2-moe-a2.7b"), 4)
    rt = Runtime(cache_len=1024)
    layers, lut_len = cfg.num_layers, cfg.moe.storage_experts

    def spec(a):
        return _spec(one_chip, a.shape, a.dtype)

    p = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    blk = p["segments"][0][0]
    blk = {**blk, "moe": {k: v for k, v in blk["moe"].items() if k != "experts"}}
    params = jax.tree.map(spec, {**p, "segments": ((blk,),)})
    state = jax.tree.map(
        spec, jax.eval_shape(lambda: zero_state(cfg, 1, rt.cache_len))
    )
    plane = (layers, _SLOTS, _D, _F)
    residency = ({
        "slots": {
            "w_gate": _spec(one_chip, plane, jnp.bfloat16),
            "w_up": _spec(one_chip, plane, jnp.bfloat16),
            "w_down": _spec(one_chip, (layers, _SLOTS, _F, _D), jnp.bfloat16),
        },
        "lut": _spec(one_chip, (layers, lut_len), jnp.int32),
    },)
    cur_len = _spec(one_chip, (), jnp.int32)
    decode = build_fused_decode_step(cfg, rt, with_demand=False)
    chunk = build_fused_prefill_step(cfg, rt, with_demand=False, with_head=False)
    return {
        "decode": decode.lower(
            params, None, _spec(one_chip, (1,), jnp.int32), state, cur_len,
            residency,
        ).compile(),
        "chunk": chunk.lower(
            params, None, _spec(one_chip, (1, 16), jnp.int32), state, cur_len,
            residency,
        ).compile(),
    }


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_fused_step_reads_only_routed_rows_on_v5e(fused_qwen2_moe, step):
    """The layer loop reads the routed rows in place: no layer's whole plane
    is sliced out of the stack, nor split by the gather emitter."""
    hlo = fused_qwen2_moe[step].as_text()
    assert "mini-gather-slice" not in hlo
    assert not re.search(rf"bf16\[{_SLOTS},", hlo)


def test_fused_decode_temp_below_one_plane_on_v5e(fused_qwen2_moe):
    temp = fused_qwen2_moe["decode"].memory_analysis().temp_size_in_bytes
    assert temp < _PLANE_BYTES
