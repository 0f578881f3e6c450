"""Slot grouped matmul — the paper's compute hot-spot, TPU-native.

Expert FFN compute addressed *through the slot planes*: the kernel receives
the routed rows sorted by group (``x [M, K]``), the slot weight store in HBM
(``w [P, K, N]``, e.g. a stacked ``[L, S+1, K, N]`` plane flattened, which is
a free reshape), each group's row count and the plane it multiplies. The
group metadata is a **scalar-prefetch** operand, so Mosaic starts each weight
tile's HBM->VMEM DMA at ``plane[group]`` before the grid step runs: every
used plane is read once per row tile of its group, straight from where it
lies, and a plane no row picks is never read. This is the TPU embodiment of
the patent's "lookup-table mapping structure": rotation rewrites the LUT,
compute never changes.

The grid walks *visits*, from megablox's ``make_group_metadata``
(``jax.experimental.pallas.ops.tpu.megablox.gmm``): one per (group, row tile) pair that a non-empty group's rows touch,
in row order, so an empty group starts no DMA and no compute, and the grid's
length is the number of visits (at least one). Output rows that belong to no
group (rows past the last group: the routed picks that missed) are zero.

bf16 operands go to the MXU as they are, accumulating in an f32 VMEM tile.
int8 slots: weights stored int8, cast to the row dtype in VMEM (exact),
per-output-channel f32 scales applied to the accumulator tile.

int4 slots (Q4_K_M analog, ``repro.quant``): weights stored as two nibbles
per uint8 byte along the reduction axis with per-group f16 scale + min. The
kernel unpacks and dequantizes IN VMEM right after the slot tile's HBM->VMEM
DMA — the affine dequant must run before the dot (scales vary along the
contraction dim, unlike int8's output-channel scales). On this CPU host the
same kernel body executes under ``interpret=True``.

Tiling: grid (N/bn, visits, K/bk), K innermost accumulating into the f32
scratch. By default a row tile holds up to 128 rows, and a weight tile is the
whole of the plane's smaller axis by ``_WEIGHT_BLOCK`` rows or columns of the
larger (1.4 MB of bf16 at Qwen1.5-MoE's 2048 x 1408 experts), so a grid step
moves about a megabyte of weights.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from repro.quant import unpack_int4

_WEIGHT_BLOCK = 512


def _gmm_kernel(weight_tile: Callable, scaled: bool, tm: int, nk: int):
    """Kernel factory. ``weight_tile(w_ref, *quant_refs)`` loads the [bk, bn]
    weight tile the rows multiply (dequantized for int4); ``scaled`` applies
    int8's per-output-channel scales to the accumulator."""

    def kernel(offsets, group, tile, planes, x_ref, w_ref, *rest):
        *quant, o_ref, acc_ref = rest
        v, k = pl.program_id(1), pl.program_id(2)

        @pl.when(k == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        w = weight_tile(w_ref, *quant)
        x = x_ref[...]
        acc_ref[...] += jnp.dot(x.astype(w.dtype) if w.dtype != x.dtype else x,
                                w, preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _():
            g = group[v]
            row = tile[v] * tm + jax.lax.broadcasted_iota(
                jnp.int32, acc_ref.shape, 0)
            mine = (row >= offsets[g]) & (row < offsets[g + 1])
            acc = acc_ref[...] * quant[0][...] if scaled else acc_ref[...]
            o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])

    return kernel


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_k", "block_n", "interpret")
)
def slot_gmm(
    x: jax.Array,                    # [M, K] rows sorted by group
    w: jax.Array,                    # [P, K, N] (bf16/int8) or [P, K/2, N] (int4 packed)
    group_sizes: jax.Array,          # [G] int32 rows per group, in row order
    group_planes: jax.Array,         # [G] int32 plane of w each group multiplies
    scale: Optional[jax.Array] = None,   # [P, N] f32 (int8) | [P, K/G, N] f16 (int4)
    mn: Optional[jax.Array] = None,      # [P, K/G, N] f16 (int4 group mins)
    *,
    block_m: int = 128,
    block_k: Optional[int] = None,
    block_n: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Rows ``x[off[g]:off[g+1]] @ w[group_planes[g]]`` for each group ``g``
    (``off`` the running sum of ``group_sizes``), as [M, N]; rows past the
    last group are zero. bf16/f32 weights give ``x.dtype`` rows, quantized
    ones f32."""
    m, k = x.shape
    is_int4 = w.dtype == jnp.uint8
    n = w.shape[2]
    assert w.shape[1] * (2 if is_int4 else 1) == k, (w.shape, k)
    big = _WEIGHT_BLOCK
    bk = block_k or (min(big, k) if k > n and k % big == 0 else k)
    bn = block_n or (min(big, n) if n >= k and n % big == 0 else n)
    if is_int4:
        assert scale is not None and mn is not None, (
            "int4 slots require per-group scales and mins"
        )
        group = k // scale.shape[1]
        # the packed tile and its scale/min tiles must stay aligned: bk spans
        # whole bytes AND whole scale groups, else take the full axis
        if bk % 2 or bk % group:
            bk = k
    assert k % bk == 0 and n % bn == 0, f"dims ({k},{n}) must divide blocks ({bk},{bn})"
    tm = min(block_m, m)
    mp = -(-m // tm) * tm
    if mp != m:
        x = jnp.concatenate([x, jnp.zeros((mp - m, k), x.dtype)])
    nk = k // bk
    g = group_sizes.shape[0]
    (offsets, vgroup, vtile), num_visits = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=mp, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=g, visit_empty_groups=False)
    # at least one visit, so the grid is never empty (a lone visit of an empty
    # group writes nothing)
    num_visits = jnp.maximum(num_visits, 1)
    planes = group_planes.astype(jnp.int32)

    def w_map(ni, v, ki, offsets, group, tile, planes):
        return planes[group[v]], ki, ni

    in_specs = [
        pl.BlockSpec((tm, bk), lambda ni, v, ki, o, g, t, p: (t[v], ki)),
        pl.BlockSpec((None, bk, bn), w_map),
    ]
    args = [x, w]
    out_dtype = x.dtype
    scaled = False
    if is_int4:
        in_specs[1] = pl.BlockSpec((None, bk // 2, bn), w_map)
        # Mosaic tiles the last two block dims in (8, 128) units, so the
        # scale/min planes get a unit axis before the output-channel axis:
        # their blocks then end in (1, bn), which equals the array's (1, N)
        # tiling instead of cutting a row of 1 or bk/G out of an 8-row tile.
        # (widened to f32: Mosaic cannot load a 16-bit tile one row high)
        qspec = pl.BlockSpec(
            (None, bk // group, 1, bn),
            lambda ni, v, ki, o, g, t, p: (p[g[v]], ki, 0, ni),
        )
        in_specs += [qspec, qspec]
        args += [a.astype(jnp.float32)[:, :, None, :] for a in (scale, mn)]
        out_dtype = jnp.float32

        def weight_tile(w_ref, s_ref, m_ref):
            # unpack two nibbles per byte in VMEM (the packing invariant lives
            # in repro.quant; the tile is its generic [.., P, N] case), widened
            # to int32 first: Mosaic has no uint8 -> f32 cast. The affine
            # dequant runs BEFORE the dot: scales vary along the contraction
            # dim, so they cannot fold into the accumulator like int8's.
            q = unpack_int4(w_ref[...].astype(jnp.int32)).astype(jnp.float32)

            def expand(ref):
                t = ref[...]
                return jnp.broadcast_to(t, (t.shape[0], group, bn)).reshape(bk, bn)

            return q * expand(s_ref) + expand(m_ref)
    elif w.dtype == jnp.int8:
        assert scale is not None, "int8 slots require per-channel scales"
        in_specs.append(pl.BlockSpec(
            (None, 1, bn), lambda ni, v, ki, o, g, t, p: (p[g[v]], 0, ni)
        ))
        args.append(scale[:, None, :])
        out_dtype = jnp.float32
        scaled = True

        def weight_tile(w_ref, s_ref):
            return w_ref[...].astype(x.dtype)       # |q| <= 127: exact
    else:
        def weight_tile(w_ref):
            return w_ref[...]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // bn, num_visits, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, bn), lambda ni, v, ki, o, g, t, p: (t[v], ni)),
        scratch_shapes=[pltpu.VMEM((tm, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        _gmm_kernel(weight_tile, scaled, tm, nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="slot_gmm",
    )(offsets, vgroup, vtile, planes, *args)
    # rows of no group were never written (row tiles no group touches, or
    # past the last group inside a touched tile)
    live = jnp.arange(mp)[:, None] < offsets[-1]
    return jnp.where(live, out, 0)[:m]


def expert_ffn(x: jax.Array, slots: dict, matmul: Callable) -> jax.Array:
    """The expert FFN over grouped rows, given ``matmul(rows, name)``, one
    grouped matmul against ``slots[name]``: gated (``w_gate``) or GELU."""
    if "w_gate" in slots:
        h = jax.nn.silu(matmul(x, "w_gate")) * matmul(x, "w_up")
    else:
        h = jax.nn.gelu(matmul(x, "w_up"))
    return matmul(h.astype(x.dtype), "w_down")


def moe_slot_ffn(
    x: jax.Array,                    # [M, D] rows sorted by group
    slots: dict,                     # w_gate/w_up/w_down (+ scale_* / min_*), [P, ...]
    group_sizes: jax.Array,
    group_planes: jax.Array,
    *,
    interpret: bool = False,
    **blocks,
) -> jax.Array:
    """Full expert FFN through the slot store: three slot_gmm calls + gating."""
    def matmul(rows, name):
        return slot_gmm(
            rows, slots[name], group_sizes, group_planes,
            slots.get(f"scale_{name}"), slots.get(f"min_{name}"),
            interpret=interpret, **blocks,
        )

    return expert_ffn(x, slots, matmul)
