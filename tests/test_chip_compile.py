"""Compile-only rehearsals of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jaxlib, and it compiles for a chip that is
described rather than attached: these tests lower each kernel at the widths
of the models the repo serves and hand it to that compiler, so a block shape
Mosaic refuses fails here instead of on the chip. Nothing runs; a pass says
nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every test worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.moe_gmm import slot_gmm
from repro.kernels.topk_gate import topk_gate


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
