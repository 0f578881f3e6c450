"""Composable decoder: assembles any ModelConfig's segment stack into
train / prefill / decode entry points.

Layer stack = ``cfg.segments``: each segment is a unit of block kinds scanned
``reps`` times with parameters stacked on axis 0, so HLO size is independent of
depth. Decode threads a per-layer state pytree (KV caches / recurrent states)
through the same scan. The MoE FFN implementation is selected by
``Runtime.sharding.moe_impl``; decode uses the gathered per-token path which is
also the compiled half of the rotary-residency technique (slot buffers + LUT).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import get_abstract_mesh
from repro.config.base import ModelConfig, ShardingConfig
from repro.kernels.ops import route_topk
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import sampling as sampling_mod
from repro.models import rglru as rglru_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (
    Params,
    apply_mlp,
    apply_norm,
    embed_init,
    init_mlp,
    init_norm,
)

Aux = Dict[str, jax.Array]


@dataclass(frozen=True)
class Runtime:
    """Execution context threaded through the model (sharding + kernel choices)."""

    sharding: ShardingConfig = field(default_factory=ShardingConfig)
    mesh: Optional[Mesh] = None
    cache_len: int = 2048
    q_chunk: int = 512
    kv_chunk: int = 512
    loss_chunk: int = 512

    @property
    def dp_spec(self) -> Tuple[str, ...]:
        return self.sharding.dp_axes

    def constrain(self, x: jax.Array, spec: P) -> jax.Array:
        if self.mesh is None:
            return x
        mesh, spec = _strip_manual(self.mesh, spec)
        if spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _manual_axes(am) -> set:
    """Names of mesh axes that are Manual in the ambient shard_map context."""
    return {n for n, t in zip(am.axis_names, am.axis_types) if t == AxisType.Manual}


def _strip_manual(mesh, spec: P):
    """Drop mesh axes that are Manual in the current shard_map context from a
    PartitionSpec (they are already fixed there); returns (mesh_to_use, spec)
    or (mesh, None) if nothing shardable remains."""
    am = get_abstract_mesh()
    manual = _manual_axes(am)
    if not manual:
        return mesh, spec
    entries = []
    for entry in spec:
        if entry is None:
            entries.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a not in manual)
            entries.append(kept if kept else None)
        else:
            entries.append(None if entry in manual else entry)
    if all(e is None for e in entries):
        return am, None
    return am, P(*entries)


# ===========================================================================
# Init
# ===========================================================================
def _init_block(key: jax.Array, kind: str, cfg: ModelConfig, dtype: Any) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if kind in ("attn_mlp", "local_attn"):
        return {
            "ln1": init_norm(cfg.norm, cfg.d_model, dtype),
            "attn": attn.init_attention(k1, cfg.d_model, cfg.attention, dtype),
            "ln2": init_norm(cfg.norm, cfg.d_model, dtype),
            "mlp": init_mlp(cfg.mlp, k2, cfg.d_model, cfg.d_ff, dtype),
        }
    if kind == "attn_moe":
        return {
            "ln1": init_norm(cfg.norm, cfg.d_model, dtype),
            "attn": attn.init_attention(k1, cfg.d_model, cfg.attention, dtype),
            "ln2": init_norm(cfg.norm, cfg.d_model, dtype),
            "moe": moe_mod.init_moe(k2, cfg.d_model, cfg.moe, cfg.mlp, dtype),
        }
    if kind == "mlstm":
        return {
            "ln": init_norm(cfg.norm, cfg.d_model, dtype),
            "cell": xlstm_mod.init_mlstm(k1, cfg.d_model, cfg.recurrent, dtype),
        }
    if kind == "slstm":
        return {
            "ln": init_norm(cfg.norm, cfg.d_model, dtype),
            "cell": xlstm_mod.init_slstm(k1, cfg.d_model, cfg.recurrent, dtype),
        }
    if kind == "rglru":
        return {
            "ln1": init_norm(cfg.norm, cfg.d_model, dtype),
            "rec": rglru_mod.init_rglru(k1, cfg.d_model, cfg.recurrent, dtype),
            "ln2": init_norm(cfg.norm, cfg.d_model, dtype),
            "mlp": init_mlp(cfg.mlp, k2, cfg.d_model, cfg.d_ff, dtype),
        }
    raise ValueError(f"unknown block kind {kind!r}")


def _init_unit_host_experts(
    pkeys: jax.Array, kind: str, cfg: ModelConfig, dtype: Any
) -> Params:
    """One ``attn_moe`` unit's stacked params with the routed experts in host
    memory: each layer is drawn on the device from its own key, its experts
    are copied into host arrays [reps, E, ...] of ``dtype`` and dropped from
    the device before the next layer is drawn."""
    reps = pkeys.shape[0]
    rest: List[Params] = []
    experts: Dict[str, np.ndarray] = {}
    for r in range(reps):
        p = _init_block(pkeys[r], kind, cfg, dtype)
        moe = dict(p["moe"])
        for n, w in moe.pop("experts").items():
            if n not in experts:
                experts[n] = np.empty((reps,) + w.shape, w.dtype)
            experts[n][r] = np.asarray(w)
            w.delete()
        rest.append({**p, "moe": moe})
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *rest)
    stacked["moe"]["experts"] = experts
    return stacked


def init_params(
    cfg: ModelConfig, key: jax.Array, *, experts_on_host: bool = False
) -> Params:
    """Seeded parameters, stacked per segment.

    ``experts_on_host`` keeps every MoE layer's routed-expert store out of
    device memory: the experts come back as host numpy arrays in
    ``cfg.dtype`` (same values and [reps, E, ...] layout as the device init),
    for engines whose residency manager holds the expert warehouse on the
    host and uploads slots from it."""
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, len(cfg.segments) + 3)
    segments: List[Tuple[Params, ...]] = []
    for si, (unit, reps) in enumerate(cfg.segments):
        unit_params: List[Params] = []
        for pi, kind in enumerate(unit):
            pkeys = jax.random.split(jax.random.fold_in(keys[si], pi), reps)
            if experts_on_host and kind == "attn_moe":
                stacked = _init_unit_host_experts(pkeys, kind, cfg, dtype)
            else:
                stacked = jax.vmap(
                    lambda k: _init_block(k, kind, cfg, dtype)
                )(pkeys)
            unit_params.append(stacked)
        segments.append(tuple(unit_params))
    p: Params = {
        "embed": embed_init(keys[-3], (cfg.vocab_size, cfg.d_model), dtype),
        "segments": tuple(segments),
        "final_norm": init_norm(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = embed_init(keys[-2], (cfg.d_model, cfg.vocab_size), dtype)
    if cfg.frontend is not None and cfg.frontend_dim != cfg.d_model:
        p["frontend_proj"] = embed_init(keys[-1], (cfg.frontend_dim, cfg.d_model), dtype)
    return p


# ===========================================================================
# Per-layer states (decode)
# ===========================================================================
def zero_state(cfg: ModelConfig, batch: int, cache_len: int) -> Any:
    """State pytree mirroring ``segments``: per position, stacked over reps."""
    segs = []
    for unit, reps in cfg.segments:
        unit_states = []
        for kind in unit:
            st = _zero_block_state(cfg, kind, batch, cache_len)
            unit_states.append(jax.tree.map(lambda x: jnp.broadcast_to(x, (reps,) + x.shape), st))
        segs.append(tuple(unit_states))
    return tuple(segs)


def paged_zero_state(cfg: ModelConfig, num_pages: int, page_size: int) -> Any:
    """Decode state over the serving engine's PAGED KV pool: the same
    segments-mirroring pytree as :func:`zero_state`, but each KV leaf is a
    SHARED plane [reps, num_pages, page_size, Hkv, dh] addressed through
    per-row page tables (``attention_decode(page_table=...)``) instead of a
    per-row [B, cap, ...] cache. ``num_pages`` counts the scratch page the
    pool reserves at physical index 0. KV-cache-only stacks — a recurrent
    state is per-row by construction and cannot be paged."""
    dtype = jnp.dtype(cfg.dtype)
    a = cfg.attention
    segs = []
    for unit, reps in cfg.segments:
        unit_states = []
        for kind in unit:
            if kind not in ("attn_mlp", "attn_moe", "local_attn"):
                raise ValueError(
                    f"paged KV pool requires KV-cache blocks, got {kind!r}"
                )
            shape = (reps, num_pages, page_size, a.num_kv_heads, a.head_dim)
            unit_states.append(
                {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
            )
        segs.append(tuple(unit_states))
    return tuple(segs)


def _zero_block_state(cfg: ModelConfig, kind: str, batch: int, cache_len: int) -> Any:
    dtype = jnp.dtype(cfg.dtype)
    if kind in ("attn_mlp", "attn_moe", "local_attn"):
        a = cfg.attention
        cap = attn._cache_capacity(a, cache_len)
        shape = (batch, cap, a.num_kv_heads, a.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if kind == "mlstm":
        return xlstm_mod.mlstm_zero_state(batch, cfg.d_model, cfg.recurrent)
    if kind == "slstm":
        return xlstm_mod.slstm_zero_state(batch, cfg.d_model, cfg.recurrent)
    if kind == "rglru":
        return rglru_mod.rglru_zero_state(batch, cfg.d_model, cfg.recurrent)
    raise ValueError(kind)


# ===========================================================================
# Block application
# ===========================================================================
def _apply_block(
    kind: str,
    p: Params,
    cfg: ModelConfig,
    rt: Runtime,
    x: jax.Array,
    mode: str,                      # "train" | "prefill" | "chunk" | "decode"
    state: Any,
    cur_len: Optional[jax.Array],
    residency: Optional[Dict[str, jax.Array]],
    page_table: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Any, Aux]:
    b, s, d = x.shape
    aux: Aux = {}
    new_state = state
    if mode == "chunk" and kind not in ("attn_mlp", "attn_moe", "local_attn"):
        # a recurrent update consumes exactly one position of state per call;
        # chunked prefill threads a KV cache through multi-token appends
        raise ValueError(f"chunked prefill requires KV-cache blocks, got {kind!r}")
    if kind in ("attn_mlp", "attn_moe", "local_attn"):
        acfg = cfg.attention
        x_in = x                        # block input (decode telemetry: replay anchor)
        h = apply_norm(cfg.norm, p["ln1"], x)
        # §Perf iteration 3b: when head-TP is unavailable (heads don't divide
        # the model axis) shard the QUERY positions over it instead (SP) —
        # attention compute /tp with one K/V broadcast, vs 16x replication
        use_sp = (
            mode in ("train", "prefill")
            and rt.mesh is not None
            and acfg.num_heads % dict(rt.mesh.shape)[rt.sharding.tp_axis] != 0
            and x.shape[1] % dict(rt.mesh.shape)[rt.sharding.tp_axis] == 0
            and x.shape[1] >= 2048
        )
        if mode == "train":
            if use_sp:
                y = _sp_attention(p["attn"], acfg, cfg, rt, h, None)[0]
            else:
                y = attn.attention_train(
                    p["attn"], acfg, h,
                    q_chunk=rt.q_chunk, kv_chunk=rt.kv_chunk,
                    use_pallas=rt.sharding.use_pallas,
                )
        elif mode == "prefill":
            if use_sp:
                y, new_state = _sp_attention(p["attn"], acfg, cfg, rt, h, rt.cache_len)
            else:
                y, new_state = attn.attention_prefill(
                    p["attn"], acfg, h, rt.cache_len,
                    q_chunk=rt.q_chunk, kv_chunk=rt.kv_chunk,
                    use_pallas=rt.sharding.use_pallas,
                )
        elif mode == "chunk":
            y, new_state = attn.attention_prefill_chunk(
                p["attn"], acfg, h, state, cur_len
            )
        else:
            y, new_state = attn.attention_decode(
                p["attn"], acfg, h, state, cur_len,
                use_pallas=rt.sharding.use_pallas, page_table=page_table,
            )
        x = x + y
        h = apply_norm(cfg.norm, p["ln2"], x)
        if kind == "attn_moe":
            if mode in ("decode", "chunk"):
                slot_buffer = lut = layer = None
                if residency is not None:
                    slot_buffer, lut = residency["slots"], residency["lut"]
                    layer = residency.get("layer")
                h2d = h.reshape(-1, d)
                logits = moe_mod.router_logits(p["moe"], h2d)
                # the fused Pallas gate on TPU (lax.top_k elsewhere)
                ids, weights = route_topk(
                    logits, cfg.moe.top_k, normalize=cfg.moe.norm_topk_prob
                )
                moe_aux = {}
                if (mode == "decode" and residency is None and rt.mesh is not None
                        and rt.sharding.moe_impl == "epsum"):
                    # §Perf: EP decode — local experts only + one [T,D] psum,
                    # instead of all-gathering the expert store per layer
                    am = get_abstract_mesh()
                    mesh_arg = am if am.axis_names else rt.mesh
                    manual = _manual_axes(am)
                    dp_eff = tuple(a for a in rt.dp_spec if a not in manual) or None

                    def epdec_fn(p_moe, x2d, ids_, w_):
                        return moe_mod.moe_epsum_decode_local(
                            p_moe, cfg.moe, x2d, ids_, w_,
                            ep_axis=rt.sharding.tp_axis,
                        )

                    y2 = shard_map(
                        epdec_fn,
                        mesh=mesh_arg,
                        in_specs=(
                            _moe_param_specs(p["moe"], rt.sharding.tp_axis),
                            P(dp_eff, None), P(dp_eff, None), P(dp_eff, None),
                        ),
                        out_specs=P(dp_eff, None),
                        check_vma=False,
                    )(p["moe"], h2d, ids, weights)
                    miss = jnp.zeros(ids.shape, bool)
                else:
                    y2, miss = moe_mod.moe_apply_routed(
                        p["moe"], h2d, ids, weights,
                        slot_buffer=slot_buffer, lut=lut, layer=layer,
                    )
                aux["moe_miss"] = miss.sum()
                # routing telemetry for the rotary engine/predictor ("route_*"
                # keys are stacked per layer by the scan, not summed);
                # route_x anchors the engine's suffix replay at this block
                aux["route_ids"] = ids
                aux["route_weights"] = weights
                aux["route_miss"] = miss
                aux["route_h"] = h2d
                aux["route_x"] = x_in.reshape(-1, d)
                y2 = y2.reshape(b, s, d)
            else:
                impl = rt.sharding.moe_impl
                if impl == "epsum" and rt.mesh is None:
                    impl = "sorted"
                if impl == "epsum":
                    ep_size = rt.mesh.shape[rt.sharding.tp_axis]

                    def epsum_fn(p_moe, x2d):
                        return moe_mod.moe_epsum_local(
                            p_moe, cfg.moe, x2d,
                            ep_axis=rt.sharding.tp_axis, ep_size=ep_size,
                        )

                    # inside another shard_map (pod-compression) the concrete
                    # mesh is rejected and manual axes may not be mentioned —
                    # use the ambient abstract mesh and strip manual axes
                    am = get_abstract_mesh()
                    mesh_arg = am if am.axis_names else rt.mesh
                    manual = _manual_axes(am)
                    dp_eff = tuple(a for a in rt.dp_spec if a not in manual) or None
                    fn = shard_map(
                        epsum_fn,
                        mesh=mesh_arg,
                        in_specs=(
                            _moe_param_specs(p["moe"], rt.sharding.tp_axis),
                            P(dp_eff, None),
                        ),
                        out_specs=(P(dp_eff, None), P()),
                        check_vma=False,
                    )
                    y2, moe_aux = fn(p["moe"], h.reshape(-1, d))
                    y2 = y2.reshape(b, s, d)
                else:
                    y2, moe_aux = moe_mod.moe_forward(p["moe"], cfg.moe, h, impl=impl)
            aux.update({f"moe_{k}": v for k, v in moe_aux.items()})
        else:
            y2 = apply_mlp(cfg.mlp, p["mlp"], h)
        return x + y2, new_state, aux
    if kind == "mlstm":
        h = apply_norm(cfg.norm, p["ln"], x)
        if mode == "train":
            y = xlstm_mod.mlstm_train(p["cell"], h, cfg.recurrent)
        elif mode == "prefill":
            y, new_state = xlstm_mod.mlstm_prefill(p["cell"], h, cfg.recurrent)
        else:
            y, new_state = xlstm_mod.mlstm_decode(p["cell"], h, state)
        return x + y, new_state, aux
    if kind == "slstm":
        h = apply_norm(cfg.norm, p["ln"], x)
        if mode == "train":
            y = xlstm_mod.slstm_train(p["cell"], h, cfg.recurrent)
        elif mode == "prefill":
            y, new_state = xlstm_mod.slstm_prefill(p["cell"], h, cfg.recurrent)
        else:
            y, new_state = xlstm_mod.slstm_decode(p["cell"], h, state)
        return x + y, new_state, aux
    if kind == "rglru":
        h = apply_norm(cfg.norm, p["ln1"], x)
        if mode == "train":
            y = rglru_mod.rglru_train(p["rec"], h, cfg.recurrent)
        elif mode == "prefill":
            y, new_state = rglru_mod.rglru_prefill(p["rec"], h, cfg.recurrent)
        else:
            y, new_state = rglru_mod.rglru_decode(p["rec"], h, state)
        x = x + y
        h = apply_norm(cfg.norm, p["ln2"], x)
        return x + apply_mlp(cfg.mlp, p["mlp"], h), new_state, aux
    raise ValueError(kind)


def _sp_attention(
    p: Params,
    acfg,
    cfg: ModelConfig,
    rt: Runtime,
    h: jax.Array,                       # [B, S, D] normed input
    cache_len: Optional[int],           # None -> train (no cache out)
):
    """Sequence-parallel attention under shard_map: each model-axis peer runs
    the flash-dataflow chunked attention for its S/tp query slice against the
    full K/V (q_offset keeps causal/window masks exact)."""
    b, s, d = h.shape
    tp = rt.sharding.tp_axis
    tp_size = dict(rt.mesh.shape)[tp]
    q, k, v = attn._project_qkv(p, acfg, h, jnp.arange(s)[None, :])
    am = get_abstract_mesh()
    mesh_arg = am if am.axis_names else rt.mesh
    manual = _manual_axes(am)
    dp_eff = tuple(a for a in rt.dp_spec if a not in manual) or None
    s_loc = s // tp_size

    def local(qc, kf, vf):
        off = jax.lax.axis_index(tp) * s_loc
        return attn.chunked_attention(
            qc, kf, vf,
            causal=True, window=acfg.window, soft_cap=acfg.logit_soft_cap,
            q_chunk=min(rt.q_chunk, s_loc), kv_chunk=rt.kv_chunk, q_offset=off,
        )

    ctx = shard_map(
        local,
        mesh=mesh_arg,
        in_specs=(
            P(dp_eff, tp, None, None),
            P(dp_eff, None, None, None),
            P(dp_eff, None, None, None),
        ),
        out_specs=P(dp_eff, tp, None, None),
        check_vma=False,
    )(q, k, v)
    y = ctx.reshape(b, s, -1) @ p["wo"]
    if cache_len is None:
        return y, None
    cap = attn._cache_capacity(acfg, cache_len)
    ck = jnp.zeros((b, cap, acfg.num_kv_heads, acfg.head_dim), k.dtype)
    cv = jnp.zeros((b, cap, acfg.num_kv_heads, acfg.head_dim), v.dtype)
    if acfg.window is not None and s > cap:
        start = s - cap
        slots = (start + jnp.arange(cap)) % cap
        ck = ck.at[:, slots].set(k[:, -cap:])
        cv = cv.at[:, slots].set(v[:, -cap:])
    else:
        ck = jax.lax.dynamic_update_slice(ck, k[:, : min(s, cap)], (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v[:, : min(s, cap)], (0, 0, 0, 0))
    return y, {"k": ck, "v": cv}


def _moe_param_specs(p: Params, tp_axis: str) -> Any:
    """shard_map in_specs for MoE params: experts sharded on E, rest replicated."""
    specs = {"router": P(None, None)}
    specs["experts"] = {k: P(tp_axis, None, None) for k in p["experts"]}
    if "shared" in p:
        specs["shared"] = {k: P(None, None) for k in p["shared"]}
        specs["shared_gate"] = P(None, None)
    return specs


def _remat_policy(name: str):
    if name == "none":
        return None
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    if name == "dots_saveable":
        return jax.checkpoint_policies.dots_saveable
    raise ValueError(f"unknown remat policy {name!r}")


# ===========================================================================
# Stack
# ===========================================================================
def _run_stack(
    cfg: ModelConfig,
    params: Params,
    rt: Runtime,
    x: jax.Array,
    mode: str,
    state: Optional[Any],
    cur_len: Optional[jax.Array],
    residency: Optional[Any],
    page_table: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Any, Aux]:
    """Scan the segment stack. residency: per-MoE-layer {slots, lut} stacked
    over reps; ``page_table`` [B, pages] switches decode-mode KV blocks to the
    paged pool layout (shared across layers — every layer's plane is carved
    identically, so one table addresses them all).

    The stacked slot planes ([reps, S+1, ...]) are closed over, not scanned:
    the scan carries each rep's layer index beside its LUT row, and the MoE
    block reads only its routed rows at (layer, slot). Scanning the planes as
    xs would copy every layer's whole plane each step."""
    aux_tot: Dict[str, jax.Array] = {}
    new_states: List[Any] = []
    for si, (unit, reps) in enumerate(cfg.segments):
        unit_params = params["segments"][si]
        # scan xs must be uniform pytrees: {} stands in for "no state"/"no residency"
        unit_state = state[si] if state is not None else tuple({} for _ in unit)
        unit_res, planes = {}, None
        if residency is not None and any(k == "attn_moe" for k in unit):
            planes = residency[si]["slots"]
            unit_res = {"lut": residency[si]["lut"],
                        "layer": jnp.arange(reps, dtype=jnp.int32)}

        def unit_fn(x, per_rep, unit=unit, planes=planes):
            p_list, s_list, r = per_rep
            r = {"slots": planes, **r} if r else None
            new_s = []
            aux_u: Dict[str, jax.Array] = {}
            for pi, kind in enumerate(unit):
                st = s_list[pi] if s_list[pi] else None
                res_i = r if kind == "attn_moe" else None
                x, ns, aux_b = _apply_block(
                    kind, p_list[pi], cfg, rt, x, mode, st, cur_len, res_i,
                    page_table,
                )
                new_s.append(ns if ns is not None else {})
                for k, v in aux_b.items():
                    if k.startswith("route_"):
                        aux_u[k] = v            # passed through, stacked by scan
                    else:
                        aux_u[k] = aux_u.get(k, jnp.zeros(())) + v
            return x, (tuple(new_s), aux_u)

        policy = _remat_policy(rt.sharding.remat_policy)
        if mode == "train" and policy is not None:
            unit_fn = jax.checkpoint(unit_fn, policy=policy)

        x, (seg_states, seg_aux) = jax.lax.scan(
            unit_fn, x, (unit_params, unit_state, unit_res)
        )
        new_states.append(seg_states)
        for k, v in seg_aux.items():
            if k.startswith("route_"):
                aux_tot[f"{k}/seg{si}"] = v      # [reps, ...] per-layer telemetry
            else:
                aux_tot[k] = aux_tot.get(k, 0.0) + v.sum()
        x = rt.constrain(x, P(rt.dp_spec, None, None))
    return x, tuple(new_states), aux_tot


# ===========================================================================
# Embedding / head
# ===========================================================================
def embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["embed"], tokens, axis=0)


def _prepend_frontend(
    cfg: ModelConfig, params: Params, x: jax.Array, frontend: Optional[jax.Array]
) -> jax.Array:
    if cfg.frontend is None:
        return x
    assert frontend is not None, f"{cfg.name} requires frontend embeddings"
    fe = frontend.astype(x.dtype)
    if "frontend_proj" in params:
        fe = fe @ params["frontend_proj"]
    return jnp.concatenate([fe, x], axis=1)


def lm_logits(cfg: ModelConfig, params: Params, h: jax.Array) -> jax.Array:
    h = apply_norm(cfg.norm, params["final_norm"], h)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return h @ head


# ===========================================================================
# Entry points
# ===========================================================================
def forward_train(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    rt: Runtime,
    frontend: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Aux]:
    """tokens [B, S_tok] -> hidden [B, S_total, D] (pre-head), aux losses."""
    x = embed_tokens(cfg, params, tokens)
    x = _prepend_frontend(cfg, params, x, frontend)
    x = rt.constrain(x, P(rt.dp_spec, None, None))
    h, _, aux = _run_stack(cfg, params, rt, x, "train", None, None, None)
    return h, aux


def lm_loss(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    labels: jax.Array,
    rt: Runtime,
    frontend: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Aux]:
    """Next-token cross-entropy, chunked over sequence so [B,S,V] never
    materializes (matters at vocab 256k). labels [B, S_tok] with -1 = ignore."""
    h, aux = forward_train(cfg, params, tokens, rt, frontend)
    f = cfg.frontend_len if cfg.frontend is not None else 0
    if f > 0:
        pred_h = h[:, f - 1 : -1]            # predicts every token position
        tgt = labels
    else:
        pred_h = h[:, :-1]
        tgt = labels[:, 1:]
    b, s, d = pred_h.shape
    chunk = min(rt.loss_chunk, s)
    n = s // chunk
    rem = s - n * chunk
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    hn = apply_norm(cfg.norm, params["final_norm"], pred_h)

    def chunk_loss(hc, tc):
        logits = (hc @ head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(tc, 0)[..., None], axis=-1)[..., 0]
        valid = (tc >= 0).astype(jnp.float32)
        return ((logz - gold) * valid).sum(), valid.sum()

    def body(carry, xs):
        hc, tc = xs
        l, c = chunk_loss(hc, tc)
        return (carry[0] + l, carry[1] + c), None

    hc = hn[:, : n * chunk].reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    tc = tgt[:, : n * chunk].reshape(b, n, chunk).transpose(1, 0, 2)
    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (hc, tc))
    if rem:
        l, c = chunk_loss(hn[:, n * chunk :], tgt[:, n * chunk :])
        tot, cnt = tot + l, cnt + c
    loss = tot / jnp.maximum(cnt, 1.0)
    if cfg.has_moe:
        m = cfg.moe
        loss = loss + m.router_aux_coef * aux.get("moe_load_balance", 0.0) / max(
            cfg.num_layers, 1
        )
        loss = loss + m.router_z_coef * aux.get("moe_router_z", 0.0) / max(cfg.num_layers, 1)
    aux["lm_loss"] = loss
    return loss, aux


def prefill_model(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,
    rt: Runtime,
    frontend: Optional[jax.Array] = None,
    last_index: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Any]:
    """Returns (last-position logits [B, V], decode state).

    ``last_index`` [B] selects each row's true last position (right-padded
    ragged prefill from the serving engine); default = final position.
    """
    x = embed_tokens(cfg, params, tokens)
    x = _prepend_frontend(cfg, params, x, frontend)
    x = rt.constrain(x, P(rt.dp_spec, None, None))
    state = zero_state(cfg, x.shape[0], rt.cache_len)
    h, state, _ = _run_stack(cfg, params, rt, x, "prefill", state, None, None)
    if last_index is None:
        hb = h[:, -1]
    else:
        hb = h[jnp.arange(h.shape[0]), last_index]
    logits = lm_logits(cfg, params, hb[:, None])[:, 0]
    return logits, state


def decode_model(
    cfg: ModelConfig,
    params: Params,
    token: jax.Array,            # [B] int32 current token
    state: Any,
    cur_len: jax.Array,          # scalar int32: number of tokens already in cache
    rt: Runtime,
    residency: Optional[Any] = None,
    page_table: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Any, Aux]:
    """One decode step: returns (logits [B, V], new state, aux incl. miss
    counts). ``page_table`` [B, pages]: ``state`` is the serving engine's
    paged pool (:func:`paged_zero_state`) instead of a per-row batch cache."""
    x = embed_tokens(cfg, params, token[:, None])
    x = rt.constrain(x, P(rt.dp_spec, None, None))
    h, state, aux = _run_stack(
        cfg, params, rt, x, "decode", state, cur_len, residency, page_table
    )
    logits = lm_logits(cfg, params, h[:, -1:])[:, 0]
    return logits, state, aux


def prefill_chunk_model(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,           # [B, C] int32: the chunk's token positions
    state: Any,
    cur_len: jax.Array,          # scalar or [B] int32: tokens already cached
    rt: Runtime,
    residency: Optional[Any] = None,
    with_head: bool = True,
) -> Tuple[Optional[jax.Array], Any, Aux]:
    """One prefill chunk: append ``C`` prompt positions to the decode state.

    The multi-token sibling of :func:`decode_model` — the same stacked scan,
    ``"chunk"`` mode blocks (:func:`attention_prefill_chunk` appends the
    chunk's KV; the MoE half runs the routed/gathered path over all B*C chunk
    tokens, optionally through the residency slot LUT, emitting the same
    ``route_*`` telemetry decode does). Requires KV-cache-only block kinds.

    Returns (logits [B, V] at the chunk's LAST position, new state, aux);
    ``with_head=False`` skips the lm-head GEMM and returns ``None`` logits —
    only a prompt's FINAL chunk needs the head, and at real vocab sizes the
    [D, V] GEMM plus the [B, V] pull is the dominant per-chunk waste.
    """
    x = embed_tokens(cfg, params, tokens)
    x = rt.constrain(x, P(rt.dp_spec, None, None))
    h, state, aux = _run_stack(cfg, params, rt, x, "chunk", state, cur_len, residency)
    if not with_head:
        return None, state, aux
    logits = lm_logits(cfg, params, h[:, -1:])[:, 0]
    return logits, state, aux


def decode_window(
    cfg: ModelConfig,
    params: Params,
    token: jax.Array,            # [B] int32 first token of the window
    state: Any,
    cur_len: jax.Array,          # scalar or [B] int32: tokens already in cache
    rt: Runtime,
    k_steps: int,
    residency: Optional[Any] = None,
    aux_fn: Optional[Any] = None,
    page_table: Optional[jax.Array] = None,
    sample: Optional[sampling_mod.SampleParams] = None,
    rng_keys: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, Any, Aux]:
    """``k_steps`` self-drafted decode steps in ONE traced program.

    A ``lax.scan`` over :func:`decode_model` threads (token, state, cur_len)
    through the window: each position runs the whole stack at its own
    ``cur_len`` (scalar engine or per-row [B] serving batches) and drafts the
    next token on-device — the self-drafting half of the speculative decode
    path. The residency pytree is a scan constant, so every window position
    gathers from the SAME residency snapshot (rotation is the caller's job, at
    window boundaries).

    Drafting is a plain argmax by default. With ``sample`` (a static
    :class:`repro.models.sampling.SampleParams`) and ``rng_keys`` ([B, 2]
    uint32 per-row base keys), position j instead draws from the warped
    distribution keyed by ``fold_in(row_key, cur_len_at_j)`` — the stateless
    position-keyed protocol that makes spec-K streams bit-identical to
    single-token ones — and the stacked aux gains ``sample_probs`` [K, B, V]
    (the warped per-position distributions, draft AND verifier for a
    self-drafting window) plus ``sample_p`` [K, B] (the drawn token's prob).

    Returns ``(draft [K, B], last_logits [B, V] f32, new_state, aux)`` where
    ``draft[j]`` is drafted from position j's logits (the token position j+1
    consumed) and every aux entry is stacked with a leading window axis [K, ...].
    ``aux_fn`` (optional) post-processes each position's aux dict before
    stacking (the engine's on-device demand GEMM). Logits are carried in f32 —
    a lossless upcast, so the caller's host argmax matches the single-token
    step bit-for-bit. ``page_table`` (scan constant, like residency) runs the
    window over the paged KV pool.
    """
    b = token.shape[0]
    logits0 = jnp.zeros((b, cfg.vocab_size), jnp.float32)

    def body(carry, _):
        tok, st, cl, _ = carry
        logits, st, aux = decode_model(
            cfg, params, tok, st, cl, rt, residency=residency,
            page_table=page_table,
        )
        if aux_fn is not None:
            aux = aux_fn(aux)
        if sample is None:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            nxt, probs, p_tok = sampling_mod.sample_step(
                logits, rng_keys, cl, sample
            )
            aux = dict(aux)
            aux["sample_probs"] = probs
            aux["sample_p"] = p_tok
        return (nxt, st, cl + 1, logits.astype(jnp.float32)), (nxt, aux)

    init = (
        jnp.asarray(token, jnp.int32),
        state,
        jnp.asarray(cur_len, jnp.int32),
        logits0,
    )
    (_, state, _, logits), (draft, aux) = jax.lax.scan(
        body, init, None, length=k_steps
    )
    return draft, logits, state, aux


# ===========================================================================
# KV window snapshot / rollback (speculative decode truncation)
# ===========================================================================
_KV_KINDS = ("attn_mlp", "attn_moe", "local_attn")


def _kv_window_slots(
    cache: jax.Array, cur_len: jax.Array, k_steps: int
) -> Tuple[jax.Array, jax.Array]:
    """Row/slot index arrays for the ``k_steps`` cache slots a decode window
    starting at ``cur_len`` writes. cache [reps, B, cap, Hkv, dh]."""
    cap, b = cache.shape[2], cache.shape[1]
    assert k_steps <= cap, (
        f"speculative window ({k_steps}) exceeds KV capacity ({cap})"
    )
    cl = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    slots = (cl[:, None] + jnp.arange(k_steps, dtype=jnp.int32)[None, :]) % cap
    return jnp.arange(b)[:, None], slots                    # [B, 1], [B, K]


def _kv_window_slots_paged(
    cache: jax.Array, page_table: jax.Array, cur_len: jax.Array, k_steps: int
) -> Tuple[jax.Array, jax.Array]:
    """Physical (page, offset) index arrays for the ``k_steps`` PAGED cache
    positions a decode window starting at ``cur_len`` writes.
    cache [reps, P, page_size, Hkv, dh]; page_table [B, cap // page_size]."""
    ps = cache.shape[2]
    b = page_table.shape[0]
    cap = page_table.shape[1] * ps
    assert k_steps <= cap, (
        f"speculative window ({k_steps}) exceeds KV capacity ({cap})"
    )
    cl = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    slots = (cl[:, None] + jnp.arange(k_steps, dtype=jnp.int32)[None, :]) % cap
    pages = jnp.take_along_axis(page_table, slots // ps, axis=1)    # [B, K]
    return pages, slots % ps                                        # [B, K] x2


def snapshot_kv_window(cfg: ModelConfig, state: Any, cur_len: jax.Array,
                       k_steps: int,
                       page_table: Optional[jax.Array] = None) -> Any:
    """Pre-window copies of the KV slots the next ``k_steps`` decode positions
    overwrite — the substrate :func:`rollback_kv_window` restores from.

    Mirrors the stacked decode-state layout (segments x unit positions), with
    {} at non-KV positions; each KV leaf becomes [reps, B, K, Hkv, dh]. A tiny
    gather (K slots per layer), so speculation can truncate exactly: full
    caches get their zeros back, ring caches their previous-lap entries (which
    a rejected window's writes would otherwise destroy).

    ``page_table`` [B, pages]: ``state`` is the paged pool — the same [reps,
    B, K, Hkv, dh] saved layout, gathered through physical (page, offset)
    coordinates instead of per-row slots.
    """
    segs = []
    for si, (unit, reps) in enumerate(cfg.segments):
        unit_saved = []
        for pi, kind in enumerate(unit):
            if kind in _KV_KINDS:
                def take(c):
                    if page_table is None:
                        rows, slots = _kv_window_slots(c, cur_len, k_steps)
                        return c[:, rows, slots]
                    pages, poff = _kv_window_slots_paged(
                        c, page_table, cur_len, k_steps
                    )
                    return c[:, pages, poff]
                unit_saved.append(jax.tree.map(take, state[si][pi]))
            else:
                unit_saved.append({})
        segs.append(tuple(unit_saved))
    return tuple(segs)


def rollback_kv_window(
    cfg: ModelConfig,
    state: Any,
    saved: Any,
    cur_len: jax.Array,
    k_steps: int,
    keep: jax.Array,             # scalar or [B]: window positions to keep
    page_table: Optional[jax.Array] = None,
) -> Any:
    """KV truncate after a partially rejected speculative window.

    Restores the pre-window contents (``saved``, from
    :func:`snapshot_kv_window`) of every cache slot written by window offsets
    ``>= keep`` — per-row ``keep`` supports ragged serving batches — leaving
    offsets ``< keep`` (the accepted prefix) in place. Truncate-then-redecode
    is bit-identical to never having speculated: the restored state matches
    the one a sequential decode would hold at length ``cur_len + keep``.

    ``page_table`` [B, pages]: paged-pool variant (scatter through physical
    (page, offset) coordinates; pad rows' duplicate scratch-page writes are
    harmless — scratch contents are never scored unmasked).
    """
    offs = jnp.arange(k_steps, dtype=jnp.int32)
    segs = []
    for si, (unit, reps) in enumerate(cfg.segments):
        unit_new = []
        for pi, kind in enumerate(unit):
            st = state[si][pi]
            if kind in _KV_KINDS:
                def roll(c, s):
                    if page_table is None:
                        rows, slots = _kv_window_slots(c, cur_len, k_steps)
                        b = c.shape[1]
                    else:
                        rows, slots = _kv_window_slots_paged(
                            c, page_table, cur_len, k_steps
                        )
                        b = page_table.shape[0]
                    kp = jnp.broadcast_to(jnp.asarray(keep, jnp.int32), (b,))
                    mask = offs[None, :] >= kp[:, None]             # [B, K]
                    cur = c[:, rows, slots]
                    blended = jnp.where(mask[None, :, :, None, None], s, cur)
                    return c.at[:, rows, slots].set(blended)
                unit_new.append(jax.tree.map(roll, st, saved[si][pi]))
            else:
                unit_new.append(st)
        segs.append(tuple(unit_new))
    return tuple(segs)
