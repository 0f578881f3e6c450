"""Mixture-of-Experts FFN: router, dispatch implementations, residency hooks.

Dispatch implementations (ShardingConfig.moe_impl):

* ``dense``  — GShard-style one-hot dispatch/combine einsums with per-batch-row
  capacity. Simple, shards predictably under plain jit (tokens over dp, experts
  over model), but the dispatch einsum itself costs O(T*E*C*D) FLOPs — it is the
  *baseline* the perf loop improves on.
* ``sorted`` — single-device sort-based dispatch: argsort assignments by expert,
  scatter into an [E, C, D] buffer, batched expert GEMMs, weighted scatter-add
  combine. O(T*k*D) data movement, zero dispatch FLOPs. Used by the rotary engine
  and as the per-device body of ``epsum``.
* ``epsum``  — expert parallelism under shard_map: all-gather tokens over the EP
  axis, each device runs ``sorted`` dispatch for its local experts, partial
  outputs reduce-scatter back. Predictable collectives (1 AG + 1 RS per layer).

Decode uses ``moe_gathered``: per-token expert weights are *gathered* (optionally
through the rotary slot LUT) and applied as grouped GEMVs — exactly active-param
FLOPs, no capacity padding. This is the compiled half of the paper's technique;
misses surface as a mask the engine corrects between steps.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import MoEConfig
from repro.models.layers import Params, dense_init

Aux = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------
def init_moe(key: jax.Array, d_model: int, mcfg: MoEConfig, mlp_kind: str, dtype: Any) -> Params:
    kr, kg, ku, kd, ksg, ksu, ksd, kgate = jax.random.split(key, 8)
    # expert weights stored [storage_experts, ...] (padded with never-routed
    # dummies when the expert count doesn't divide the EP axis)
    e, f = mcfg.storage_experts, mcfg.expert_d_ff
    p: Params = {"router": dense_init(kr, (d_model, mcfg.num_experts), jnp.float32)}
    # fan-in is each expert's own reduction dim, never the leading expert axis
    if mlp_kind == "swiglu":
        p["experts"] = {
            "w_gate": dense_init(kg, (e, d_model, f), dtype, fan_in=d_model),
            "w_up": dense_init(ku, (e, d_model, f), dtype, fan_in=d_model),
            "w_down": dense_init(kd, (e, f, d_model), dtype, fan_in=f),
        }
    else:
        p["experts"] = {
            "w_up": dense_init(ku, (e, d_model, f), dtype, fan_in=d_model),
            "w_down": dense_init(kd, (e, f, d_model), dtype, fan_in=f),
        }
    if mcfg.num_shared_experts > 0:
        sf = mcfg.shared_d_ff * mcfg.num_shared_experts  # fused shared experts
        p["shared"] = {
            "w_gate": dense_init(ksg, (d_model, sf), dtype),
            "w_up": dense_init(ksu, (d_model, sf), dtype),
            "w_down": dense_init(ksd, (sf, d_model), dtype, fan_in=sf),
        }
        p["shared_gate"] = dense_init(kgate, (d_model, 1), dtype)
    return p


def expert_param_bytes(d_model: int, mcfg: MoEConfig, mlp_kind: str, dtype_bytes: int = 2) -> int:
    """Bytes of ONE routed expert (the unit of residency)."""
    mats = 3 if mlp_kind == "swiglu" else 2
    return mats * d_model * mcfg.expert_d_ff * dtype_bytes


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------
def router_logits(p: Params, x2d: jax.Array) -> jax.Array:
    """x2d [T, D] -> router logits f32 [T, E]."""
    return x2d.astype(jnp.float32) @ p["router"]


def topk_route(logits: jax.Array, mcfg: MoEConfig) -> Tuple[jax.Array, jax.Array, Aux]:
    """logits [T,E] -> (ids [T,k] int32, weights [T,k] f32, aux losses)."""
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, mcfg.top_k)
    if mcfg.norm_topk_prob:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    e = mcfg.num_experts
    # Switch-style load-balance loss + router z-loss
    frac_tokens = jnp.mean(
        (jax.nn.one_hot(ids, e, dtype=jnp.float32)).sum(axis=1), axis=0
    )  # [E] fraction routed (counting multiplicity/k handled by scale)
    mean_prob = jnp.mean(probs, axis=0)
    aux: Aux = {
        "load_balance": e * jnp.sum(frac_tokens / mcfg.top_k * mean_prob),
        "router_z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
    }
    return ids.astype(jnp.int32), weights, aux


def _expert_ffn(experts: Params, xs: jax.Array) -> jax.Array:
    """Batched expert FFN. xs [E, C, D] against stacked weights -> [E, C, D].
    bf16 operands, f32 accumulation (MXU-native mixed precision)."""
    def mm(a, w):
        return jnp.einsum("ecd,edf->ecf", a, w,
                          preferred_element_type=jnp.float32).astype(a.dtype)

    if "w_gate" in experts:
        h = jax.nn.silu(mm(xs, experts["w_gate"])) * mm(xs, experts["w_up"])
    else:
        h = jax.nn.gelu(mm(xs, experts["w_up"]))
    return jnp.einsum("ecf,efd->ecd", h, experts["w_down"],
                      preferred_element_type=jnp.float32).astype(xs.dtype)


def _shared_ffn(p: Params, x: jax.Array) -> jax.Array:
    sp = p["shared"]
    if "w_gate" in sp:
        h = jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])
    else:
        h = jax.nn.gelu(x @ sp["w_up"])
    y = h @ sp["w_down"]
    gate = jax.nn.sigmoid(x @ p["shared_gate"])
    return y * gate


# ---------------------------------------------------------------------------
# dense: GShard one-hot dispatch (per batch row)
# ---------------------------------------------------------------------------
def moe_dense(p: Params, mcfg: MoEConfig, x: jax.Array) -> Tuple[jax.Array, Aux]:
    """x [B, S, D] -> [B, S, D]. Per-row capacity C = ceil(S*k/E * cf)."""
    b, s, d = x.shape
    e, k = mcfg.storage_experts, mcfg.top_k
    cap = max(k, int(math.ceil(s * k / mcfg.num_experts * mcfg.capacity_factor)))
    logits = router_logits(p, x.reshape(-1, d))        # [T, num_experts]
    ids, weights, aux = topk_route(logits, mcfg)       # ids < num_experts
    ids = ids.reshape(b, s, k)
    weights = weights.reshape(b, s, k)

    # position of each assignment within its expert, per batch row, k-major
    onehot = jax.nn.one_hot(ids, e, dtype=jnp.int32)             # [B,S,k,E]
    flat = onehot.transpose(0, 2, 1, 3).reshape(b, s * k, e)     # k-major order
    pos = jnp.cumsum(flat, axis=1) - 1                            # [B,S*k,E]
    pos = (pos * flat).sum(-1).reshape(b, k, s).transpose(0, 2, 1)  # [B,S,k]
    keep = pos < cap

    disp = (
        jax.nn.one_hot(ids, e, dtype=x.dtype)[..., None]
        * jax.nn.one_hot(pos, cap, dtype=x.dtype)[..., None, :]
        * keep[..., None, None].astype(x.dtype)
    ).sum(axis=2)                                                  # [B,S,E,C]
    combine = (
        jax.nn.one_hot(ids, e, dtype=jnp.float32)[..., None]
        * jax.nn.one_hot(pos, cap, dtype=jnp.float32)[..., None, :]
        * (weights * keep.astype(jnp.float32))[..., None, None]
    ).sum(axis=2)                                                  # [B,S,E,C] f32

    expert_in = jnp.einsum("bsec,bsd->becd", disp, x)              # [B,E,C,D]
    expert_out = jax.vmap(_expert_ffn, in_axes=(None, 0))(p["experts"], expert_in)
    y = jnp.einsum("becd,bsec->bsd", expert_out.astype(jnp.float32), combine)
    y = y.astype(x.dtype)
    if mcfg.num_shared_experts > 0:
        y = y + _shared_ffn(p, x)
    return y, aux


# ---------------------------------------------------------------------------
# sorted: scatter-based local dispatch (zero dispatch FLOPs)
# ---------------------------------------------------------------------------
def sorted_dispatch(
    x2d: jax.Array, ids: jax.Array, num_experts: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Build [E, C, D] expert batches by sort + scatter.

    Returns (buffer [E,C,D], dest [T*k] flat slot per assignment or -1 if dropped,
    tok [T*k] source token per assignment).
    """
    t, k = ids.shape
    flat_e = ids.reshape(-1)                                   # [T*k]
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)        # [T*k]
    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    tok_sorted = tok[order]
    # position within expert group = index - start_of_group
    counts = jnp.bincount(flat_e, length=num_experts)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[e_sorted].astype(jnp.int32)
    keep = pos < capacity
    slot = jnp.where(keep, e_sorted * capacity + pos, num_experts * capacity)  # overflow row
    buf = jnp.zeros((num_experts * capacity + 1, x2d.shape[-1]), x2d.dtype)
    buf = buf.at[slot].set(x2d[tok_sorted], mode="drop")
    # invert the sort so dest/tok align with the original [T*k] assignment order
    dest = jnp.zeros((t * k,), jnp.int32).at[order].set(jnp.where(keep, slot, -1))
    return buf[:-1].reshape(num_experts, capacity, -1), dest, tok


def moe_sorted(
    p: Params, mcfg: MoEConfig, x2d: jax.Array, capacity: Optional[int] = None
) -> Tuple[jax.Array, Aux]:
    """x2d [T, D] -> [T, D] via sort-based dispatch on a single device."""
    t, d = x2d.shape
    e, k = mcfg.storage_experts, mcfg.top_k
    cap = capacity or max(
        k, int(math.ceil(t * k / mcfg.num_experts * mcfg.capacity_factor))
    )
    logits = router_logits(p, x2d)
    ids, weights, aux = topk_route(logits, mcfg)
    buf, dest, tok = sorted_dispatch(x2d, ids, e, cap)
    out = _expert_ffn(p["experts"], buf)                       # [E,C,D]
    flat_out = out.reshape(e * cap, d)
    w_flat = weights.reshape(-1)
    valid = dest >= 0
    contrib = flat_out[jnp.where(valid, dest, 0)] * (
        w_flat * valid.astype(jnp.float32)
    )[:, None].astype(out.dtype)
    y = jnp.zeros((t, d), jnp.float32).at[tok].add(contrib.astype(jnp.float32))
    y = y.astype(x2d.dtype)
    if mcfg.num_shared_experts > 0:
        y = y + _shared_ffn(p, x2d)
    aux["dropped_frac"] = 1.0 - valid.mean()
    return y, aux


# ---------------------------------------------------------------------------
# epsum: expert parallelism under shard_map (AG tokens -> local sorted -> RS)
# ---------------------------------------------------------------------------
def moe_epsum_local(
    p_local: Params, mcfg: MoEConfig, x_local: jax.Array, *, ep_axis: str, ep_size: int
) -> Tuple[jax.Array, Aux]:
    """Per-device body under shard_map. x_local [T, D] = this data-row's tokens,
    REPLICATED across the EP axis; experts sharded on E.

    Every EP peer routes the row's tokens identically (router weights are
    replicated — the [T,E] GEMM is cheap), runs sorted dispatch restricted to
    its local experts, and the partial expert outputs are summed with ONE
    all-reduce over the EP axis per layer. No token all-to-all, no duplicated
    expert compute: each token's expert FLOPs happen exactly once, on the
    expert's owner.
    """
    e, k = mcfg.num_experts, mcfg.top_k
    e_loc = p_local["experts"]["w_up"].shape[0]   # storage_experts / ep_size
    my = jax.lax.axis_index(ep_axis)
    t, d = x_local.shape
    logits = router_logits(p_local, x_local)
    ids, weights, aux = topk_route(logits, mcfg)
    # map global (storage-space) expert -> local index (or E_loc => not mine)
    lo = my * e_loc
    local_ids = jnp.where((ids >= lo) & (ids < lo + e_loc), ids - lo, e_loc)
    cap = max(k, int(math.ceil(t * k / e * mcfg.capacity_factor)))
    buf, dest, tok = sorted_dispatch(x_local, local_ids, e_loc + 1, cap)
    out = _expert_ffn(p_local["experts"], buf[:e_loc])                  # [E_loc,C,D]
    flat_out = out.reshape(e_loc * cap, d)
    w_flat = weights.reshape(-1)
    valid = (dest >= 0) & (dest < e_loc * cap)
    contrib = flat_out[jnp.where(valid, dest, 0)] * (
        w_flat * valid.astype(jnp.float32)
    )[:, None].astype(out.dtype)
    y_partial = jnp.zeros((t, d), jnp.float32).at[tok].add(contrib.astype(jnp.float32))
    y = jax.lax.psum(y_partial.astype(x_local.dtype), ep_axis)
    if mcfg.num_shared_experts > 0:
        y = y + _shared_ffn(p_local, x_local)   # shared experts replicated over EP
    return y, aux


# ---------------------------------------------------------------------------
# gathered decode: per-token expert weights, optionally through the slot LUT
# ---------------------------------------------------------------------------
def _slot_rows(
    plane: jax.Array, gidx: jax.Array, layer: Optional[jax.Array]
) -> jax.Array:
    """The rows ``plane[layer, gidx]`` ([L, S+1, ...] stacked plane) or
    ``plane[gidx]`` ([S+1, ...] one layer's plane), as [*gidx.shape, ...].

    Each routed pick is one ``lax.dynamic_slice`` of its row, so the plane is
    read where it lies: a stacked plane closed over by the layer scan is never
    sliced or copied per layer, only the picked rows move.
    """
    row = plane.shape[-2:]
    lead = (layer,) if plane.ndim == 4 else ()
    sizes = (1,) * (len(lead) + 1) + row

    def one(g):
        start = lead + (g,) + (0,) * len(row)
        return jax.lax.dynamic_slice(plane, start, sizes).reshape(row)

    return jax.vmap(one)(gidx.reshape(-1)).reshape(gidx.shape + row)


def moe_apply_routed(
    p: Params,
    x2d: jax.Array,
    ids: jax.Array,                       # [T, k] int32 (precomputed routing)
    weights: jax.Array,                   # [T, k] f32
    *,
    slot_buffer: Optional[Params] = None,
    lut: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
    include_shared: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Apply already-routed experts via gathered weights (engine path).

    Same compute as ``moe_gathered`` but routing is supplied by the caller so the
    rotary engine can resolve the LUT / issue blocking loads BEFORE compute.
    ``slot_buffer`` planes are one layer's ([S+1, ...]) or the whole stack's
    ([L, S+1, ...], read at ``layer``); either way only the routed rows are
    read (:func:`_slot_rows`). Returns (y [T,D], miss [T,k]).
    """
    if slot_buffer is not None:
        assert lut is not None
        num_slots = slot_buffer["w_up"].shape[-3] - 1
        slots = lut[ids]
        miss = slots >= num_slots
        gidx = jnp.where(miss, num_slots, slots)
        w = {n: _slot_rows(v, gidx, layer) for n, v in slot_buffer.items()}
    else:
        miss = jnp.zeros(ids.shape, bool)
        w = {n: jnp.take(v, ids, axis=0) for n, v in p["experts"].items()}
    wq, wd = w["w_up"], w["w_down"]
    if "w_gate" in w:
        wg = w["w_gate"]
        h = jax.nn.silu(jnp.einsum("td,tkdf->tkf", x2d, wg)) * jnp.einsum(
            "td,tkdf->tkf", x2d, wq
        )
    else:
        h = jax.nn.gelu(jnp.einsum("td,tkdf->tkf", x2d, wq))
    outs = jnp.einsum("tkf,tkfd->tkd", h, wd)
    w_eff = weights * (~miss).astype(jnp.float32)
    y = jnp.einsum("tkd,tk->td", outs.astype(jnp.float32), w_eff).astype(x2d.dtype)
    if include_shared and "shared" in p:
        y = y + _shared_ffn(p, x2d)
    return y, miss


def moe_gathered(
    p: Params,
    mcfg: MoEConfig,
    x2d: jax.Array,
    *,
    slot_buffer: Optional[Params] = None,
    lut: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, Aux]:
    """Decode-path MoE: gather each routed expert's weights and apply as GEMVs.

    ``slot_buffer``: stacked expert weights restricted to resident slots
      ({w_gate/w_up/w_down} with leading dim num_slots+1; the trailing slot is a
      zero "miss" slot). ``lut`` [E] int32 maps expert id -> slot (missing ->
      num_slots). When both are None, gathers from the full expert store.

    Returns (y [T,D], miss_mask [T,k] bool — which routed experts were NOT
    resident; weight mass of misses is dropped here and corrected by the engine).
    """
    logits = router_logits(p, x2d)
    ids, weights, aux = topk_route(logits, mcfg)
    y, miss = moe_apply_routed(p, x2d, ids, weights, slot_buffer=slot_buffer, lut=lut)
    return y, miss, aux


def moe_epsum_decode_local(
    p_local: Params,
    mcfg: MoEConfig,
    x_local: jax.Array,          # [T, D] this data-row's decode tokens (replicated over EP)
    ids: jax.Array,              # [T, k] routing (computed outside; router replicated)
    weights: jax.Array,          # [T, k]
    *,
    ep_axis: str,
) -> jax.Array:
    """EP decode without gathering expert weights (§Perf iteration 1).

    Each EP peer applies only its LOCAL experts to the routed tokens via the
    gathered per-token path (T is tiny in decode), partials summed with one
    [T, D] psum — wire bytes per layer drop from O(E·D·F) weight gathers to
    O(T·D).
    """
    e_loc = p_local["experts"]["w_up"].shape[0]
    my = jax.lax.axis_index(ep_axis)
    lo = my * e_loc
    # combine weight per (token, local expert): sum over the k routed picks
    mine = (ids >= lo) & (ids < lo + e_loc)                      # [T, k]
    onehot = jax.nn.one_hot(
        jnp.where(mine, ids - lo, e_loc), e_loc + 1, dtype=jnp.float32
    )[..., :e_loc]                                                # [T, k, E_loc]
    w_mask = jnp.einsum("tke,tk->te", onehot, weights)            # [T, E_loc]
    # dense over local experts: every local expert's weights stream HBM->MXU
    # exactly once per step (decode's true lower bound when >=1 token routes
    # to it); T x E_loc is tiny so the extra FLOPs are noise next to that
    src = p_local["experts"]
    def mm(a, w, eq):
        return jnp.einsum(eq, a, w,
                          preferred_element_type=jnp.float32).astype(a.dtype)
    if "w_gate" in src:
        h = jax.nn.silu(mm(x_local, src["w_gate"], "td,edf->tef")) * mm(
            x_local, src["w_up"], "td,edf->tef")
    else:
        h = jax.nn.gelu(mm(x_local, src["w_up"], "td,edf->tef"))
    outs = mm(h, src["w_down"], "tef,efd->ted")                   # [T, E_loc, D]
    y_partial = jnp.einsum("ted,te->td", outs.astype(jnp.float32), w_mask)
    y = jax.lax.psum(y_partial.astype(x_local.dtype), ep_axis)
    if mcfg.num_shared_experts > 0:
        y = y + _shared_ffn(p_local, x_local)
    return y


def moe_forward(
    p: Params,
    mcfg: MoEConfig,
    x: jax.Array,
    *,
    impl: str = "dense",
    ep_axis: Optional[str] = None,
    ep_size: int = 1,
) -> Tuple[jax.Array, Aux]:
    """Shape-polymorphic entry: x [B,S,D] -> [B,S,D]."""
    b, s, d = x.shape
    if impl == "dense":
        return moe_dense(p, mcfg, x)
    if impl == "sorted":
        y, aux = moe_sorted(p, mcfg, x.reshape(-1, d))
        return y.reshape(b, s, d), aux
    if impl == "epsum":
        assert ep_axis is not None
        y, aux = moe_epsum_local(p, mcfg, x.reshape(-1, d), ep_axis=ep_axis, ep_size=ep_size)
        return y.reshape(b, s, d), aux
    raise ValueError(f"unknown moe impl {impl!r}")
