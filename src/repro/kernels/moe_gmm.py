"""Slot-LUT grouped matmul — the paper's compute hot-spot, TPU-native.

Expert FFN compute addressed *through the rotating slot buffer*: the kernel
receives per-expert token tiles, the slot weight store (HBM), and the
expert->slot LUT as a **scalar-prefetch** operand, so Mosaic can issue the slot
weight tile's HBM->VMEM DMA using ``lut[e]`` before the grid step runs. This is
the TPU embodiment of the patent's "lookup-table mapping structure": rotation
rewrites the LUT, compute never changes.

int8 slots: weights stored int8, per-output-channel f32 scales applied to the
MXU accumulator tile — dequantization costs one VPU multiply per output
element and the slot buffer's HBM footprint halves vs bf16.

int4 slots (Q4_K_M analog, ``repro.quant``): weights stored as two nibbles
per uint8 byte along the reduction axis with per-group f16 scale + min. The
kernel unpacks and dequantizes IN VMEM right after the slot tile's HBM->VMEM
DMA — the affine dequant must run before the dot (scales vary along the
contraction dim, unlike int8's output-channel scales), costing a few VPU ops
per element while the slot buffer's HBM footprint and the host->HBM upload
both shrink ~4x vs bf16. On this CPU host the same kernel body executes under
``interpret=True``.

Tiling: grid (E, C/bc, F/bf, D/bd), D innermost accumulating into a VMEM f32
scratch tile; (bc, bf, bd) default to 128 — MXU-aligned on all three dims.
int4 blocks additionally keep bd a multiple of the scale group so the packed
tile and its scale/min tiles stay aligned.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant import unpack_int4


def _gmm_kernel(lut_ref, x_ref, w_ref, o_ref, acc_ref):
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0].astype(jnp.float32),
        w_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(d == pl.num_programs(3) - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _gmm_kernel_int8(lut_ref, x_ref, w_ref, scale_ref, o_ref, acc_ref):
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0].astype(jnp.float32),
        w_ref[0].astype(jnp.float32),          # int8 -> f32 in VREG
        preferred_element_type=jnp.float32,
    )

    @pl.when(d == pl.num_programs(3) - 1)
    def _():
        # per-output-channel dequant on the accumulator tile
        o_ref[0] = (acc_ref[...] * scale_ref[0]).astype(o_ref.dtype)


def _gmm_kernel_int4(group: int):
    """Kernel factory: ``group`` rows share one f16 scale/min (static)."""

    def kernel(lut_ref, x_ref, w_ref, scale_ref, mn_ref, o_ref, acc_ref):
        d = pl.program_id(3)

        @pl.when(d == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # unpack two nibbles per byte in VMEM (the packing invariant lives in
        # repro.quant; the kernel tile is its generic [.., P, F] case)
        # (widened to int32 first: Mosaic has no uint8 -> f32 cast)
        q = unpack_int4(w_ref[0].astype(jnp.int32)).astype(jnp.float32)
        bd, bf = q.shape

        # affine dequant BEFORE the dot: scales vary along the contraction
        # dim, so they cannot fold into the accumulator like int8's. The
        # [bd/G, 1, bf] scale/min tiles broadcast over each group's rows.
        def expand(ref):
            t = ref[0]
            return jnp.broadcast_to(t, (t.shape[0], group, bf)).reshape(bd, bf)

        s = expand(scale_ref)
        m = expand(mn_ref)
        acc_ref[...] += jnp.dot(
            x_ref[0].astype(jnp.float32),
            q * s + m,
            preferred_element_type=jnp.float32,
        )

        @pl.when(d == pl.num_programs(3) - 1)
        def _():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_f", "block_d", "interpret")
)
def slot_gmm(
    x: jax.Array,                    # [E, C, D]
    w: jax.Array,                    # [S+1, D, F] (bf16/int8) or [S+1, D/2, F] (int4 packed)
    lut: jax.Array,                  # [E] int32
    scale: Optional[jax.Array] = None,   # [S+1, F] f32 (int8) | [S+1, D/G, F] f16 (int4)
    mn: Optional[jax.Array] = None,      # [S+1, D/G, F] f16 (int4 group mins)
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 128,
    interpret: bool = False,
) -> jax.Array:
    e, c, d = x.shape
    is_int4 = w.dtype == jnp.uint8
    s1, dw, f = w.shape
    if is_int4:
        dw *= 2
    assert dw == d, (dw, d)
    bc, bf, bd = min(block_c, c), min(block_f, f), min(block_d, d)
    if is_int4:
        assert scale is not None and mn is not None, (
            "int4 slots require per-group scales and mins"
        )
        group = d // scale.shape[1]
        # the packed tile and its scale/min tiles must stay aligned: bd spans
        # whole bytes AND whole scale groups, else take the full axis
        if bd % 2 or bd % group:
            bd = d
    assert c % bc == 0 and f % bf == 0 and d % bd == 0, (
        f"dims ({c},{f},{d}) must divide blocks ({bc},{bf},{bd})"
    )
    grid = (e, c // bc, f // bf, d // bd)
    out_dtype = jnp.float32 if w.dtype in (jnp.int8, jnp.uint8) else x.dtype

    in_specs = [
        pl.BlockSpec((1, bc, bd), lambda e, ci, fi, di, lut: (e, ci, di)),
        pl.BlockSpec((1, bd, bf), lambda e, ci, fi, di, lut: (lut[e], di, fi)),
    ]
    kernel = _gmm_kernel
    args = (lut, x, w)
    # Mosaic tiles the last two block dims in (8, 128) units, so the
    # scale/min planes get a unit axis before the output-channel axis: their
    # blocks then end in (1, bf), which equals the array's (1, F) tiling
    # instead of cutting a row of 1 or bd/G out of an 8-row tile.
    if is_int4:
        in_specs[1] = pl.BlockSpec(
            (1, bd // 2, bf), lambda e, ci, fi, di, lut: (lut[e], di, fi)
        )
        qspec = pl.BlockSpec(
            (1, bd // group, 1, bf), lambda e, ci, fi, di, lut: (lut[e], di, 0, fi)
        )
        in_specs += [qspec, qspec]
        kernel = _gmm_kernel_int4(group)
        # (widened to f32 here: Mosaic cannot load a 16-bit tile one row high)
        args = (lut, x, w) + tuple(
            a.astype(jnp.float32)[:, :, None, :] for a in (scale, mn)
        )
    elif w.dtype == jnp.int8:
        assert scale is not None, "int8 slots require per-channel scales"
        in_specs.append(pl.BlockSpec(
            (1, 1, bf), lambda e, ci, fi, di, lut: (lut[e], 0, fi)
        ))
        kernel = _gmm_kernel_int8
        args = (lut, x, w, scale[:, None, :])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, ci, fi, di, lut: (e, ci, fi)),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, c, f), out_dtype),
        interpret=interpret,
        name="slot_gmm",
    )(*args)


def moe_slot_ffn(
    x: jax.Array,                    # [E, C, D] dispatched tokens
    slots: dict,                     # w_gate/w_up/w_down (+ scale_* / min_*)
    lut: jax.Array,
    *,
    interpret: bool = False,
    **blocks,
) -> jax.Array:
    """Full expert FFN through the slot store: three slot_gmm calls + gating."""
    def g(name, xx):
        return slot_gmm(
            xx, slots[name], lut, slots.get(f"scale_{name}"),
            slots.get(f"min_{name}"),
            interpret=interpret, **blocks,
        )

    if "w_gate" in slots:
        h = jax.nn.silu(g("w_gate", x)) * g("w_up", x)
    else:
        h = jax.nn.gelu(g("w_up", x))
    return g("w_down", h.astype(x.dtype))
