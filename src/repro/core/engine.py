"""RotaryEngine: the paper-faithful per-layer decode engine.

Execution per decode step (the paper's §4 loop, DESIGN.md §2 "engine path"):

  embed -> for each layer:
    attn half (device jit) -> fused router top-k ON DEVICE (Pallas topk_gate on
    TPU, lax.top_k on CPU) -> gathered slot compute against the
    persistent device LUT (misses classified in-kernel, dropped) ->
    pre-gating: layer l's hidden predicts layer l+1's demand; the manager
    rotates l+1's slots and issues uploads BEFORE l+1 executes (double-buffered
    prefetch — transfers hide behind layer l's compute in the clock model)
  -> lm head -> sample.

The full model weights live in host memory (numpy); only attention/static
weights plus each layer's slot group are device-resident, mirroring Figure 1.

Fused decode hot path (default for non-LRU policies on KV-cache stacks)
-----------------------------------------------------------------------
One compiled whole-stack step per token: ``build_fused_decode_step`` wraps
``tfm.decode_model``'s ``lax.scan`` over the segment stack (embed -> every
layer -> lm head) in a single jit, consuming the manager's version-keyed
``stacked_residency()`` pytree. The KV state is DONATED to the step
(``donate_argnums``), so decode updates the caches in place instead of copying
them every token. Demand prediction runs on-device inside the same step: the
per-layer router matrices are stacked once (``predictor.next_layer_routers``)
and every layer's next-step demand (softmaxed, token-averaged) comes back as
one small ``demand_next`` [L, E] tensor. Routing / miss / demand telemetry is
pulled with async copies that overlap the queued compute; the only
queue-draining device->host transfer per token is the final logits pull, and
the only compiled-program launch per miss-free token is the step itself
(O(1) dispatches instead of O(layers)). The host's per-token work shrinks to
rotation bookkeeping: EMA fold, ring transition, and batched slot uploads
(one donated scatter per weight tensor per rotated layer).

Speculative multi-token decode (``spec_k > 1``)
-----------------------------------------------
Greedy decode can advance K tokens per launch: ``build_fused_window_step``
scans the fused step over a K-position self-drafting window (per-position
``cur_len``, donated KV state carried across positions, next token = on-device
argmax) against ONE residency snapshot, so a miss-free window costs one
compiled launch and one queue-draining pull for K tokens. Acceptance is
greedy (self-drafting with identical weights verifies against its own
argmaxes — ``serving.sampler.greedy_accept`` is the plug point for real
drafters; the stochastic rule is a hook): rejection comes only from residency
misses, which invalidate a position and everything drafted after it. The
first rejected position rolls the KV cache back (``tfm.rollback_kv_window``
restores the pre-window slot contents captured by ``tfm.snapshot_kv_window`` —
ring caches need real restoration, not just masking) and replays exactly like
a missed single-token step; rotation is deferred to window boundaries, where
``rotate_window_from_telemetry`` applies the committed steps' transitions
one-by-one-equivalently while coalescing uploads to one batched scatter per
layer per window.

Exactness under misses is preserved by REPLAY: the fused step is the
optimistic pass; when the end-of-step miss masks show a routed expert was not
resident, the suffix from the first missed layer re-executes with the
per-layer walk against the SAME residency the compiled step gathered from
(rotation happens strictly after replay), anchored on the per-layer block
inputs the step emits as telemetry (``route_x``). Re-running an attention
block overwrites the same KV slot, so the post-step donated state is a valid
replay substrate — which is why the fused path requires KV-cache-only block
kinds; MoE stacks with recurrent blocks fall back to the per-layer hot walk
below. Tokens match the per-layer sync path for every policy (on replayed
steps the demand predictor saw the optimistic hiddens — the mechanism is
unchanged, only its input differs).

Chunked prefill hot path (``prefill_chunk=C``)
----------------------------------------------
Prompts ingest in power-of-two chunks (``prefill_chunk_plan`` bounds the
compile cache): each chunk is ONE compiled whole-stack launch
(``build_fused_prefill_step`` wrapping ``tfm.prefill_chunk_model`` — chunk
attention appends to the donated KV state, the MoE half gathers all B*C
chunk tokens through the same ``stacked_residency()`` pytree decode uses)
plus ONE queue-draining pull and ONE coalesced rotation window at the chunk
boundary (the pre-gating demand GEMM over the chunk's stacked hiddens, EMA
fold, ring transition per layer, uploads batched to one scatter per weight
tensor per rotated layer). A missed chunk suffix-replays per layer from the
first missed layer's saved block input, exactly like decode. Per-layer
engines (host_routing / LRU / ``fused_decode=False``) walk the same chunks
layer-by-layer with the same boundary rotation — the benchmark baseline —
and because both paths drive rotation through the SAME compiled demand
program, residency (and therefore the miss pattern) evolves identically:
fused-chunk logits and post-prefill KV are bit-identical to the chunked
layer walk, including slot-starved and int8/int4 stores.

Per-layer hot walk (fallback) and legacy switches
-------------------------------------------------
The PR-1 per-layer hot path (jitted attention half + routed MoE half per
layer, async telemetry copies, one logits pull per token, saved-input replay)
survives for MoE stacks with recurrent state. ``host_routing=True``
reproduces the seed engine (blocking logits pull + numpy softmax/top-k + LUT
re-upload per layer — kept as the benchmark baseline), and LRU residency
automatically uses the per-layer sync walk because its reactive blocking
loads need routed ids on host mid-step.

Exactness invariant and telemetry→transition map
------------------------------------------------
THE contract every fast path in this module keeps: greedy outputs are
bit-identical to full residency — rotation, speculation, chunking and
quantized stores may change WHERE compute happens and WHAT moves over the
link, never what comes out (quantized stores are exactness-clean within
their format: the host correction GEMMs against dequant∘quant weights).
The mechanisms are suffix replay (fused decode, chunked prefill) and KV
rollback + replay (speculative windows); ``docs/ARCHITECTURE.md`` has the
full dispatch-count table. Telemetry consumers on the host:
``route_ids``/``route_weights`` feed ``DemandPredictor.observe`` and
hit/miss accounting; ``route_miss`` picks the replay start layer;
``demand_next`` (decode, on-device GEMM) and the chunk-boundary demand GEMM
(prefill) feed ``DemandPredictor.update`` → ``policy.prepare`` → ring
transition → ``SlotStore.write_batch``; ``route_x`` anchors replay;
``route_h`` is the prefill demand GEMM's input.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig, ResidencyConfig
from repro.core.predictor import DemandPredictor, host_topk_route
from repro.core.residency import RotaryResidencyManager
from repro.core.stats import EngineStats
from repro.core.transfer import CostModel, TransferClock
from repro.kernels.ops import route_topk
from repro.models import transformer as tfm
from repro.models import moe as moe_mod
from repro.models import sampling as sampling_mod
from repro.models.sampling import SampleParams
from repro.models.layers import apply_norm
from repro.models.transformer import Runtime
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracer import resolve_tracer


def _np_ffn(w: Dict[str, np.ndarray], x: np.ndarray) -> np.ndarray:
    """Host GEMM of ONE expert (the paper's CPU-resident expert execution):
    its weights are upcast to f32 here, per call — the warehouse itself stays
    in the model dtype."""
    xf = x.astype(np.float32)
    if "w_gate" in w:
        g = xf @ w["w_gate"].astype(np.float32)
        h = (g / (1.0 + np.exp(-g))) * (xf @ w["w_up"].astype(np.float32))
    else:
        u = xf @ w["w_up"].astype(np.float32)
        h = 0.5 * u * (1.0 + np.tanh(np.sqrt(2 / np.pi) * (u + 0.044715 * u**3)))
    return h @ w["w_down"].astype(np.float32)


def moe_segments(cfg: ModelConfig) -> List[int]:
    """Indices of segments containing an ``attn_moe`` unit — the order the
    scan stacks per-layer ``route_*`` telemetry in (MoE-ordinal order)."""
    return [
        si for si, (unit, _) in enumerate(cfg.segments)
        if any(k == "attn_moe" for k in unit)
    ]


def concat_route_telemetry(
    aux: Dict[str, jax.Array], name: str, moe_segs: List[int], axis: int = 0
) -> np.ndarray:
    """Per-segment ``route_{name}/seg*`` aux -> one [L, ...] host array in
    MoE-ordinal order (shared by RotaryEngine and ServingEngine). Speculative
    windows stack a leading K axis, so their layer axis is ``axis=1``."""
    if len(moe_segs) == 1:
        return np.asarray(aux[f"route_{name}/seg{moe_segs[0]}"])
    return np.concatenate(
        [np.asarray(aux[f"route_{name}/seg{si}"]) for si in moe_segs], axis=axis
    )


def split_expert_store(
    cfg: ModelConfig, params: Any
) -> Tuple[Any, List[Dict[str, np.ndarray]], List[np.ndarray]]:
    """Split ``params`` for an engine with a residency manager.

    Returns ``(device_params, host_experts, routers)``: the params tree with
    every MoE block's routed-expert store removed (what compiled steps take —
    slot stores supply expert weights), the per-MoE-layer host expert store
    ``{w_*: [E, ...]}`` in ``cfg.dtype``, and the per-MoE-layer f32 router
    matrices, both in MoE-ordinal order. Host experts that already are numpy
    arrays in ``cfg.dtype`` (``init_params(experts_on_host=True)``) are
    sliced without a copy; device experts are copied to host once."""
    dtype = np.dtype(jnp.dtype(cfg.dtype))
    segs: List[Tuple[Any, ...]] = []
    host_experts: List[Dict[str, np.ndarray]] = []
    routers: List[np.ndarray] = []
    for si, (unit, reps) in enumerate(cfg.segments):
        unit_p = list(params["segments"][si])
        for pi, kind in enumerate(unit):
            if kind == "attn_moe":
                moe = {k: v for k, v in unit_p[pi]["moe"].items() if k != "experts"}
                unit_p[pi] = {**unit_p[pi], "moe": moe}
        for r in range(reps):
            for pi, kind in enumerate(unit):
                if kind != "attn_moe":
                    continue
                experts = params["segments"][si][pi]["moe"]["experts"]
                host_experts.append({
                    n: np.asarray(w[r], dtype) for n, w in experts.items()
                })
                routers.append(
                    np.asarray(unit_p[pi]["moe"]["router"][r], np.float32)
                )
        segs.append(tuple(unit_p))
    return {**params, "segments": tuple(segs)}, host_experts, routers


def build_fused_decode_step(
    cfg: ModelConfig,
    rt: Runtime,
    *,
    with_demand: bool,
    donate_state: bool = True,
    keep_replay_anchor: bool = True,
) -> Callable:
    """ONE compiled whole-stack decode step, shared by ``RotaryEngine`` (fused
    hot path) and ``ServingEngine`` (continuous-batching tick).

    Returns a jitted ``fn(params, routers_next, token, state, cur_len,
    residency) -> (logits [B, V], new_state, aux)``. ``cur_len`` may be a
    scalar (engine) or per-row [B] (serving's ragged batches). ``state`` is
    DONATED: the KV caches update in place instead of being copied per token.

    ``aux`` carries the per-segment ``route_*`` telemetry from the scan; with
    ``with_demand`` the DemandPredictor GEMM also runs in-graph —
    ``aux["demand_next"]`` [L, E] holds layer (l+1)%L's softmaxed,
    token-averaged demand computed from layer l's post-attention hidden
    against ``routers_next`` [L, D, E] (``predictor.next_layer_routers()``) —
    and the bulky per-layer hiddens (``route_h``) are dropped from the outputs
    since the demand signal subsumes them. ``keep_replay_anchor=False``
    additionally drops the per-layer block inputs (``route_x``) for callers
    with no replay path (the serving tick), saving their device->host copy.
    """
    moe_segs = moe_segments(cfg)
    aux_fn = _demand_aux_fn(moe_segs, with_demand, keep_replay_anchor)

    def step(params, routers_next, token, state, cur_len, residency,
             page_table=None):
        # trailing page_table (serving's paged KV pool) keeps the 6-arg
        # call signature every existing caller compiled against
        logits, new_state, aux = tfm.decode_model(
            cfg, params, token, state, cur_len, rt, residency=residency,
            page_table=page_table,
        )
        return logits, new_state, aux_fn(aux, routers_next)

    return jax.jit(step, donate_argnums=(3,) if donate_state else ())


def _demand_aux_fn(
    moe_segs: List[int], with_demand: bool, keep_replay_anchor: bool
):
    """Per-position aux hook shared by the single-token fused step and the
    speculative window: in-graph demand GEMM + telemetry slimming."""

    def aux_fn(aux, routers_next):
        if with_demand:
            h_all = jnp.concatenate(
                [aux[f"route_h/seg{si}"] for si in moe_segs], axis=0
            )                                                       # [L, T, D]
            dl = jnp.einsum("ltd,lde->lte", h_all.astype(jnp.float32), routers_next)
            aux["demand_next"] = jax.nn.softmax(dl, axis=-1).mean(axis=1)
            for si in moe_segs:
                del aux[f"route_h/seg{si}"]
                if not keep_replay_anchor:
                    del aux[f"route_x/seg{si}"]
        return aux

    return aux_fn


def prefill_chunk_plan(s: int, chunk: int) -> List[int]:
    """Split a prompt of ``s`` tokens into power-of-two chunk lengths.

    ``chunk`` (itself a power of two) repeats while the remainder allows, then
    the tail decomposes into descending powers of two — so a prompt of any
    length compiles at most ``log2(chunk)`` distinct chunk shapes beyond the
    steady-state one, keeping the fused prefill step's compile cache bounded.
    """
    assert s >= 1, "empty prompt"
    assert chunk >= 1 and (chunk & (chunk - 1)) == 0, (
        f"prefill_chunk must be a power of two, got {chunk}"
    )
    plan = [chunk] * (s // chunk)
    rem, bit, bits = s - chunk * (s // chunk), 1, []
    while rem:
        if rem & 1:
            bits.append(bit)
        rem >>= 1
        bit <<= 1
    return plan + sorted(bits, reverse=True)


def build_fused_prefill_step(
    cfg: ModelConfig,
    rt: Runtime,
    *,
    with_demand: bool,
    donate_state: bool = True,
    keep_replay_anchor: bool = True,
    with_head: bool = True,
) -> Callable:
    """ONE compiled whole-stack prefill-CHUNK step: the prompt-ingestion
    sibling of :func:`build_fused_decode_step`.

    Returns a jitted ``fn(params, routers_next, tokens [B, C], state, cur_len,
    residency) -> (logits [B, V], new_state, aux)`` wrapping
    :func:`tfm.prefill_chunk_model`: the chunk's C positions run through the
    whole stack (embed -> every layer -> lm head) in one launch, appending to
    the DONATED KV state, gathering experts for all B*C chunk tokens from the
    same ``stacked_residency()`` pytree decode uses, and emitting the same
    ``route_*`` telemetry decode does. The engine calls this with
    ``with_demand=False`` so the raw per-layer hiddens (``route_h``) stay in
    the aux for the chunk-boundary demand GEMM (which must see
    replay-corrected hiddens — an in-graph demand would bake in the
    optimistic ones) and ``with_head=False`` for every chunk but a prompt's
    last (only the final chunk's logits are consumed; the rest would pay the
    [D, V] head GEMM and a [B, V] pull for nothing). The jit re-specializes
    per chunk length; power-of-two chunk plans (:func:`prefill_chunk_plan`)
    keep that cache bounded.
    """
    moe_segs = moe_segments(cfg)
    aux_fn = _demand_aux_fn(moe_segs, with_demand, keep_replay_anchor)

    def step(params, routers_next, tokens, state, cur_len, residency):
        logits, new_state, aux = tfm.prefill_chunk_model(
            cfg, params, tokens, state, cur_len, rt, residency=residency,
            with_head=with_head,
        )
        return logits, new_state, aux_fn(aux, routers_next)

    return jax.jit(step, donate_argnums=(3,) if donate_state else ())


def build_fused_window_step(
    cfg: ModelConfig,
    rt: Runtime,
    k_steps: int,
    *,
    with_demand: bool,
    donate_state: bool = True,
    keep_replay_anchor: bool = True,
    sample: Optional[SampleParams] = None,
) -> Callable:
    """ONE compiled program running ``k_steps`` self-drafted decode
    positions (the speculative window) — the multi-token sibling of
    :func:`build_fused_decode_step`, shared by ``RotaryEngine`` and
    ``ServingEngine``.

    Returns a jitted ``fn(params, routers_next, token, state, cur_len,
    residency) -> (draft [K, B], last_logits [B, V], new_state, aux)``. The
    window scans :func:`tfm.decode_window`: per-position ``cur_len``, KV state
    DONATED and carried across positions, the next position's token drafted
    on-device (argmax, or a categorical draw from the ``sample``-warped
    distribution keyed per position when ``sample`` is set — the trailing
    ``rng_keys`` [B, 2] argument threads the per-row base keys), and every
    position gathering from the SAME residency snapshot (rotation happens at
    window boundaries). Telemetry comes back with a leading window axis —
    ``route_*`` as [K, L, T, k] after :func:`concat_route_telemetry`,
    ``demand_next`` as [K, L, E], and when sampling ``sample_probs``
    [K, B, V] / ``sample_p`` [K, B] — so the caller can commit the accepted
    prefix and roll back the rest.
    """
    moe_segs = moe_segments(cfg)
    aux_fn = _demand_aux_fn(moe_segs, with_demand, keep_replay_anchor)

    def step(params, routers_next, token, state, cur_len, residency,
             page_table=None, rng_keys=None):
        return tfm.decode_window(
            cfg, params, token, state, cur_len, rt, k_steps,
            residency=residency,
            aux_fn=lambda aux: aux_fn(aux, routers_next),
            page_table=page_table,
            sample=sample, rng_keys=rng_keys,
        )

    return jax.jit(step, donate_argnums=(3,) if donate_state else ())


def build_window_fns(
    cfg: ModelConfig,
    rt: Runtime,
    k: int,
    *,
    with_demand: bool,
    keep_replay_anchor: bool = True,
    sample: Optional[SampleParams] = None,
) -> Tuple[Callable, Callable, Callable]:
    """The compiled speculative-window triple both engines cache per K
    (and per ``sample`` warp params when sampling):
    (window step, KV snapshot, KV rollback). Rollback donates the state it
    truncates; the snapshot is dispatched BEFORE the donating window, so it
    reads the pre-window buffers."""
    step = build_fused_window_step(
        cfg, rt, k, with_demand=with_demand, donate_state=True,
        keep_replay_anchor=keep_replay_anchor, sample=sample,
    )
    # trailing page_table: the serving engine passes its paged pool + per-row
    # page tables through the same triple; contiguous callers are unchanged
    snap = jax.jit(
        lambda state, cl, page_table=None: tfm.snapshot_kv_window(
            cfg, state, cl, k, page_table=page_table
        )
    )
    roll = jax.jit(
        lambda state, saved, cl, keep, page_table=None: tfm.rollback_kv_window(
            cfg, state, saved, cl, k, keep, page_table=page_table
        ),
        donate_argnums=(0,),
    )
    return step, snap, roll


class RotaryEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        rescfg: ResidencyConfig,
        *,
        rt: Optional[Runtime] = None,
        cost: Optional[CostModel] = None,
        batch: int = 1,
        seed: int = 0,
        host_routing: bool = False,
        fused_decode: Optional[bool] = None,
        spec_k: int = 1,
        prefill_chunk: Optional[int] = None,
        prefetch: bool = False,
        trace=None,
    ):
        """Decode-path switches (see module docstring for the mechanisms):

        * default (``host_routing=False, fused_decode=None``) — fused
          whole-stack step when the policy and block kinds allow it, else the
          per-layer hot walk (LRU / recurrent stacks), always exact via replay;
        * ``fused_decode=False`` — force the per-layer device-resident hot
          walk (kept as the fused step's benchmark comparison). Prefer this
          for SLOT-STARVED configurations (num_slots well below the routed
          working set): the fused step's between-step rotation gives up the
          walk's intra-step pre-gating, and when most steps miss, the
          whole-suffix replay makes fused decode slower than the walk — see
          the slot-starved rows of ``benchmarks/decode_hot_path.py``. The
          paper's operating point (prefetch covers routing) is miss-free,
          where fused wins by construction;
        * ``fused_decode=True``  — require the fused step (raises if the
          policy or stack cannot support it);
        * ``host_routing=True``  — seed-style engine: blocking per-layer
          logits pull + numpy softmax/top-k (benchmark baseline);
        * ``spec_k=K``  (K > 1) — speculative multi-token decode: greedy
          decode runs K-position self-drafting windows through ONE compiled
          program (``build_fused_window_step``); residency misses reject the
          window's suffix, which rolls the KV cache back
          (``tfm.rollback_kv_window``) and replays the first rejected
          position exactly like the single-token path replays a missed step.
          Requires the fused path; non-greedy decode falls back to
          single-token steps (the stochastic accept rule is a hook for now —
          see ``repro.serving.sampler``);
        * ``prefill_chunk=C`` — chunked prefill hot path: the prompt ingests
          in power-of-two chunks of at most C tokens
          (``prefill_chunk_plan``). Fused engines run each chunk through ONE
          compiled launch (``build_fused_prefill_step``) with ONE coalesced
          rotation window between chunks, pre-gated by the previous chunk's
          telemetry; per-layer engines (host_routing / LRU /
          ``fused_decode=False``) walk the same chunks layer-by-layer — the
          benchmark baseline. ``None`` keeps the legacy full-sequence
          layer-walk prefill. Requires KV-cache-only block kinds (recurrent
          stacks fall back to the legacy walk); the fused chunk replay
          additionally requires window-free attention (ring caches fall back
          to the chunked walk). The fused and walk chunked paths are
          bit-identical to each other (logits AND post-prefill KV, every
          residency mode and slot format), and greedy continuations match
          the legacy full-sequence walk token for token — misses
          host-correct in the walk and suffix-replay per chunk in the fused
          path, exactly like decode;
        * ``prefetch=True`` — asynchronous predictive expert prefetch over
          double-buffered slot planes: while a launch computes, the predicted
          next transition's uploads land in a shadow generation
          (``RotaryResidencyManager.begin_prefetch``), and the boundary
          becomes confirm/correct/flip instead of synchronous scatters; the
          policy additionally steers up to ``rescfg.prefetch_margin`` cold
          slots toward predicted-hot off-window experts, which is what cuts
          the miss (and replay) rate. Residency may EVOLVE differently from
          the synchronous baseline, but greedy tokens stay bit-identical —
          the exactness machinery (host correction + replay) is unchanged.
          Requires the fused hot path; ``prefetch=False`` (the default)
          keeps the synchronous rotation path as the exactness baseline.
        * ``trace=Tracer(...)`` — record launch/pull/rotation/prefetch spans
          into a host-side ring buffer and export Chrome trace-event JSON
          (``repro.obs``). ``None`` (and a disabled tracer) leave every hot
          path untouched: emission sites are guarded ``if tr is not None``.
        """
        assert cfg.has_moe, "RotaryEngine requires an MoE architecture"
        self.cfg = cfg
        self.rescfg = rescfg
        self.rt = rt or Runtime(cache_len=1024)
        self.cost = cost or CostModel()
        self.batch = batch
        self.host_routing = host_routing
        self.stats = EngineStats()
        self.clock = TransferClock(self.cost)
        self._tr = resolve_tracer(trace)
        self.tracer = self._tr
        self.metrics = MetricsRegistry()

        # ---- flatten the layer stack; slice per-layer params -------------
        # the expert warehouse lives in host memory in EVERY residency mode
        # (full residency uploads all of it into the slot stores); device
        # params never carry it
        dev_params, self.host_experts, routers = split_expert_store(cfg, params)
        self.layers: List[Tuple[str, Any]] = []       # (kind, params)
        self.moe_index: List[Optional[int]] = []      # per layer: MoE ordinal
        self._layer_pos: List[Tuple[int, int, int]] = []   # li -> (si, pi, r)
        self._moe_pos: List[Tuple[int, int]] = []     # MoE ordinal -> (si, r)
        self._moe_layer_li: List[int] = []            # MoE ordinal -> flat li
        moe_ct = 0
        for si, (unit, reps) in enumerate(cfg.segments):
            for r in range(reps):
                for pi, kind in enumerate(unit):
                    self._layer_pos.append((si, pi, r))
                    p_l = jax.tree.map(
                        lambda a, r=r: a[r], dev_params["segments"][si][pi]
                    )
                    if kind == "attn_moe":
                        self._moe_pos.append((si, r))
                        self._moe_layer_li.append(len(self.layers))
                        self.moe_index.append(moe_ct)
                        moe_ct += 1
                    else:
                        self.moe_index.append(None)
                    self.layers.append((kind, p_l))
        self.num_moe_layers = moe_ct
        self.embed_params = {
            k: dev_params[k]
            for k in ("embed", "final_norm", "lm_head", "frontend_proj")
            if k in dev_params
        }

        # quantized host-correction rows per (MoE layer, expert), built
        # lazily by _correction_expert on that expert's first miss
        self._correct_cache: Dict[Tuple[int, int], Dict[str, np.ndarray]] = {}
        self.predictor = DemandPredictor(routers, ema=rescfg.predictor_ema)
        self.manager = RotaryResidencyManager(
            cfg, rescfg, self.host_experts,
            batch=batch, cache_len=self.rt.cache_len,
            cost=self.cost, stats=self.stats, seed=seed,
            tracer=self._tr, metrics=self.metrics,
        )
        # LRU answers misses with reactive blocking loads mid-step: that needs
        # routed ids on host before the next layer, i.e. the sync walk
        self._hot_decode = not host_routing and not any(
            getattr(p, "needs_sync_resolve", False) for p in self.manager.policies
        )
        # fused whole-stack step: additionally requires replay-safe per-layer
        # state — re-running an attention block overwrites the same KV slot,
        # while a recurrent update is destructive (see module docstring)
        kv_only = all(
            kind in ("attn_moe", "attn_mlp", "local_attn")
            for kind, _ in self.layers
        )
        fused_ok = self._hot_decode and kv_only
        if prefill_chunk is not None:
            assert prefill_chunk >= 1 and (prefill_chunk & (prefill_chunk - 1)) == 0, (
                f"prefill_chunk must be a power of two, got {prefill_chunk}"
            )
        self.prefill_chunk = prefill_chunk
        # chunked prefill threads the KV cache through multi-token appends:
        # recurrent stacks (and frontend archs, whose prompt is not plain
        # tokens) keep the legacy full-sequence walk; the fused chunk path
        # additionally needs window-free attention, because its suffix replay
        # re-reads pre-chunk cache content that a ring overwrite destroys
        self._chunk_prefill_ok = kv_only and cfg.frontend is None
        self._chunk_prefill_fused_ok = (
            self._chunk_prefill_ok and cfg.attention.window is None
        )
        if fused_decode:
            assert fused_ok, (
                "fused decode requires device routing (no host_routing, no "
                "LRU) and KV-cache-only block kinds"
            )
        self._fused_decode = fused_ok if fused_decode is None else bool(fused_decode)
        assert spec_k >= 1, "spec_k is a window size (>= 1)"
        if spec_k > 1:
            assert self._fused_decode, (
                "speculative decode (spec_k > 1) rides the fused whole-stack "
                "step: it needs device routing (no host_routing, no LRU) and "
                "KV-cache-only block kinds"
            )
            from repro.models import attention as attn_mod

            cap = attn_mod._cache_capacity(cfg.attention, self.rt.cache_len)
            assert spec_k <= cap, (
                f"spec_k={spec_k} exceeds the KV cache capacity ({cap})"
            )
        self.spec_k = spec_k
        # asynchronous predictive prefetch rides the fused hot path: it hides
        # shadow uploads under an IN-FLIGHT compiled launch, which the
        # synchronous baselines don't have. Fail loudly on unsupported combos
        # rather than silently running synchronous.
        self.prefetch = bool(prefetch)
        if self.prefetch:
            if host_routing:
                raise ValueError(
                    "prefetch=True is incompatible with host_routing=True: the "
                    "host-routing baseline blocks on per-layer logits pulls, so "
                    "there is no in-flight launch to hide shadow uploads under"
                )
            if not self._fused_decode:
                raise ValueError(
                    "prefetch=True requires the fused whole-stack hot path "
                    "(no LRU / recurrent stacks, fused_decode not disabled): "
                    "synchronous per-layer walks rotate mid-step, so there is "
                    "nothing to overlap"
                )
            if rescfg.mode != "full":
                # full residency never rotates: accept the flag (benchmarks
                # sweep it uniformly) but skip the shadow plane. margin=0:
                # predictive slot steering measured NEGATIVE on this workload
                # (routing is too close to uniform for the one-step-stale
                # signal — both the EMA and the raw pre-gating sample raised
                # the steps-with-a-miss count), so the perf mechanism is the
                # miss-relaunch, which needs no prediction at all; steering
                # stays available through the manager for richer routers
                self.manager.enable_prefetch(margin=0)
        self._jits: Dict[Tuple, Callable] = {}
        self._head_jit = jax.jit(self._lm_head_impl)
        self._cost_cache: Dict[str, Tuple[float, float]] = {}
        # stacked next-layer routers [L, D, E] + the chunk-boundary demand GEMM
        # (softmax(h_l @ R_{l+1}), token-averaged): shared by EVERY chunked
        # prefill path — walk and fused compute the pre-gating signal through
        # the SAME jitted program on the same [L, T, D] stacked hiddens, so
        # the residency evolution (and with it the miss pattern) is
        # bit-identical between them, which is what makes slot-starved
        # chunked prefill outputs bitwise comparable across paths. Built only
        # for engines that can use it (the router stack is a real device
        # upload a seed-baseline engine should not pay)
        self._chunk_telem: List[Tuple] = []        # walk-path per-chunk buffer
        if self._fused_decode or prefill_chunk is not None:
            self._routers_next = jnp.asarray(self.predictor.next_layer_routers())

            def demand_all(h_all, routers):        # [L, T, D], [L, D, E]
                dl = jnp.einsum(
                    "ltd,lde->lte", h_all.astype(jnp.float32), routers
                )
                return jax.nn.softmax(dl, axis=-1).mean(axis=1)

            self._demand_all_jit = jax.jit(demand_all)
        if self._fused_decode:
            # rotation happens strictly after replay in the fused path, so no
            # residency snapshot outlives the buffers a rotation replaces
            self.manager.donate_buffers = True
            self._fused_step = build_fused_decode_step(
                cfg, self.rt, with_demand=True, donate_state=True
            )
            # chunked prefill hot path: one whole-stack launch per chunk (the
            # jit re-specializes per power-of-two chunk length). with_demand
            # is OFF: the step keeps the raw per-layer hiddens (route_h) so
            # the chunk-boundary demand GEMM above runs on authoritative
            # (replay-corrected) hiddens, exactly like the walk baseline.
            # Only a prompt's final chunk runs the lm head — the other
            # chunks' queue-draining pull is the routing telemetry
            self._fused_prefill_step = build_fused_prefill_step(
                cfg, self.rt, with_demand=False, donate_state=True
            )
            self._fused_prefill_step_nohead = build_fused_prefill_step(
                cfg, self.rt, with_demand=False, donate_state=True,
                with_head=False,
            )
            self._moe_segs = moe_segments(cfg)
            self._pull_keys = [
                f"route_{nm}/seg{si}"
                for si in self._moe_segs
                for nm in ("ids", "weights", "miss")
            ] + ["demand_next"]
            # the prefill step has no in-graph demand: its telemetry pulls are
            # the routing triple only (route_h stays device-side for the
            # chunk-boundary demand GEMM; route_x is read only on replay)
            self._prefill_pull_keys = [
                f"route_{nm}/seg{si}"
                for si in self._moe_segs
                for nm in ("ids", "weights", "miss")
            ]
            # stacked decode params: the expert warehouse never rides along —
            # the residency arg supplies expert weights in EVERY mode
            self._decode_params = dev_params
            self._dstate = None          # stacked decode state (built by prefill)
            # speculative windows: compiled (window, snapshot, rollback) per
            # (K, sample params) — sampled windows draft with on-device draws
            self._fused_windows: Dict[Any, Tuple[Callable, Callable, Callable]] = {}
            # the snapshot exists to make rollback exact; when misses are
            # impossible (full residency) or never replayed, no window is ever
            # rejected and the pre-window gather is pure overhead
            self._spec_needs_rollback = (
                rescfg.mode != "full" and rescfg.host_compute_misses
            )
        # between-window standalone draws (cached per warp params): the SAME
        # ops/keys as the in-window draw, so sampled streams are bit-identical
        # whichever path derives a position's token
        self._sample_fns: Dict[SampleParams, Callable] = {}
        self._warm_start()

    # ------------------------------------------------------------------
    def _warm_start(self) -> None:
        """Initial residency: rotate every layer once on the uniform prior
        (cold start — 'GGUF load' analog)."""
        for li in range(self.num_moe_layers):
            self.manager.prepare_layer(li, self.predictor.smoothed[li])

    # ------------------------------------------------------------------
    # jitted pieces (one compile per (kind, mode, routed))
    # ------------------------------------------------------------------
    def _block_fn(self, kind: str, mode: str, routed: bool = True) -> Callable:
        key = (kind, mode, routed)
        if key in self._jits:
            return self._jits[key]
        cfg, rt = self.cfg, self.rt

        if kind == "attn_moe":
            m = cfg.moe

            def attn_half(p, x, state, cur_len):
                h = apply_norm(cfg.norm, p["ln1"], x)
                if mode == "decode":
                    y, new_state = tfm.attn.attention_decode(p["attn"], cfg.attention, h, state, cur_len)
                elif mode == "chunk":
                    y, new_state = tfm.attn.attention_prefill_chunk(
                        p["attn"], cfg.attention, h, state, cur_len)
                else:
                    y, new_state = tfm.attn.attention_prefill(
                        p["attn"], cfg.attention, h, rt.cache_len,
                        q_chunk=rt.q_chunk, kv_chunk=rt.kv_chunk)
                x_mid = x + y
                h2 = apply_norm(cfg.norm, p["ln2"], x_mid)
                logits = moe_mod.router_logits(p["moe"], h2.reshape(-1, x.shape[-1]))
                if routed:
                    # fused device routing: Pallas topk_gate on TPU,
                    # lax.top_k on CPU — no host round-trip
                    ids, weights = route_topk(
                        logits, m.top_k, normalize=m.norm_topk_prob
                    )
                    return x_mid, h2, ids, weights, new_state
                return x_mid, h2, logits, new_state

            def moe_half(p, x_mid, h2, ids, weights, slots, lut):
                t = ids.shape[0]
                y2, miss = moe_mod.moe_apply_routed(
                    p["moe"], h2.reshape(t, -1), ids, weights,
                    slot_buffer=slots, lut=lut)
                return x_mid + y2.reshape(x_mid.shape), miss

            fns = (jax.jit(attn_half), jax.jit(moe_half))
        else:
            def full_block(p, x, state, cur_len):
                y, new_state, _ = tfm._apply_block(
                    kind, p, cfg, rt, x, mode, state if state else None, cur_len, None)
                return y, new_state

            fns = (jax.jit(full_block),)
        self._jits[key] = fns
        return fns

    def _embed(self, tokens: jax.Array) -> jax.Array:
        self.stats.device_dispatches += 1
        return jnp.take(self.embed_params["embed"], tokens, axis=0)

    def _lm_head_impl(self, embed_params, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        hn = apply_norm(cfg.norm, embed_params["final_norm"], h)
        head = (
            embed_params["embed"].T
            if cfg.tie_embeddings
            else embed_params["lm_head"]
        )
        return hn @ head

    def _lm_head(self, h: jax.Array) -> jax.Array:
        self.stats.device_dispatches += 1
        return self._head_jit(self.embed_params, h)

    # ------------------------------------------------------------------
    # shared host-side pieces
    # ------------------------------------------------------------------
    def _correction_expert(self, moe_li: int, e: int) -> Dict[str, np.ndarray]:
        """One expert's host weights for the miss correction: the warehouse
        rows, or — under quantization — dequant(quant(w)) through the store's
        exact jnp ops, so the correction is bit-consistent with what a
        RESIDENT slot would have computed. Quantized rows are memoized per
        (layer, expert) in the model dtype on first miss (quantization is
        per expert, so a one-expert pass equals the batched one)."""
        hw = self.host_experts[moe_li]
        if self.rescfg.quantization is None:
            return {n: w[e] for n, w in hw.items()}
        rows = self._correct_cache.get((moe_li, e))
        if rows is None:
            from repro.core.slots import fake_quantized_batch

            dtype = jnp.dtype(self.cfg.dtype)
            rows = {
                n: fake_quantized_batch(
                    w[e : e + 1], self.rescfg.quantization, dtype,
                    self.rescfg.quant_group_size,
                )[0].astype(dtype)
                for n, w in hw.items()
            }
            self._correct_cache[(moe_li, e)] = rows
        return rows

    def _host_correct(
        self,
        x: jax.Array,
        moe_li: int,
        h2: jax.Array,
        ids: np.ndarray,
        weights: np.ndarray,
        miss: np.ndarray,
    ) -> jax.Array:
        """Seed-style exact host GEMM correction for missed experts (against
        the dequantized weights when the slots are quantized)."""
        h2_np = np.asarray(h2, np.float32).reshape(ids.shape[0], -1)
        corr = np.zeros_like(h2_np)
        n_host = 0
        for t_i, j in zip(*np.nonzero(miss)):
            w = self._correction_expert(moe_li, int(ids[t_i, j]))
            corr[t_i] += weights[t_i, j] * _np_ffn(w, h2_np[t_i])
            n_host += 1
        x = x + jnp.asarray(corr, x.dtype).reshape(x.shape)
        self.stats.layer(moe_li).host_computed += n_host
        self.clock.host(
            self.cost.host_compute_s(self.manager.host_expert_flops(n_host))
        )
        return x

    # ------------------------------------------------------------------
    # per-layer sync walk (prefill; decode for LRU / host_routing baseline)
    # ------------------------------------------------------------------
    def _run_layers(self, x: jax.Array, mode: str, cur_len: int) -> jax.Array:
        cfg = self.cfg
        m = cfg.moe
        clock = self.clock
        cur = jnp.int32(cur_len)
        for li, (kind, p_l) in enumerate(self.layers):
            state = self.state[li]
            if kind == "attn_moe":
                moe_li = self.moe_index[li]
                # --- routing (host baseline or device-routed pull) --------
                if self.host_routing:
                    attn_half, moe_half = self._block_fn(kind, mode, routed=False)
                    x_mid, h2, logits_dev, new_state = attn_half(p_l, x, state, cur)
                    self.stats.sync_pulls += 1
                    self.stats.device_dispatches += 1
                    logits = np.asarray(logits_dev, np.float32)
                    ids, weights = host_topk_route(
                        logits, m.top_k, normalize=m.norm_topk_prob
                    )
                else:
                    attn_half, moe_half = self._block_fn(kind, mode, routed=True)
                    x_mid, h2, ids_dev, w_dev, new_state = attn_half(p_l, x, state, cur)
                    self.stats.sync_pulls += 1
                    self.stats.device_dispatches += 1
                    ids = np.asarray(ids_dev)
                    weights = np.asarray(w_dev)
                self.state[li] = new_state
                # --- LUT resolve (LRU may block-load here) ----------------
                _, miss = self.manager.resolve(moe_li, ids, clock)
                slots_tree = self.manager.stores[moe_li].as_pytree()
                lut_dev = self.manager.device_lut(moe_li)
                x, _ = moe_half(
                    p_l, x_mid, h2,
                    jnp.asarray(ids), jnp.asarray(weights),
                    slots_tree, lut_dev,
                )
                self.stats.device_dispatches += 1
                # --- host correction for misses ---------------------------
                if miss.any() and self.rescfg.host_compute_misses:
                    x = self._host_correct(x, moe_li, h2, ids, weights, miss)
                # --- modeled device time for this layer -------------------
                flops, byts = self._layer_cost(kind, x.shape, cur_len, hits=int((~miss).sum()))
                clock.compute(self.cost.compute_s(flops, byts))
                if mode == "chunk":
                    # chunked prefill defers rotation to the chunk boundary
                    # (mirrors the fused hot path — the boundary rotation runs
                    # the shared demand GEMM on this chunk's hiddens, so walk
                    # and fused see bit-identical residency evolution)
                    self._chunk_telem.append((ids, weights, miss, h2))
                else:
                    # --- pre-gate the NEXT MoE layer from THIS hidden ------
                    # (cyclic: the last layer pre-gates layer 0 of next step)
                    nxt = (moe_li + 1) % self.num_moe_layers
                    demand = self.predictor.predict(nxt, np.asarray(h2).reshape(ids.shape[0], -1))
                    self.manager.prepare_layer(nxt, demand, clock)
                    self.predictor.observe(moe_li, ids, weights)
            else:
                (block,) = self._block_fn(kind, mode)
                x, new_state = block(p_l, x, state if state else {}, cur)
                self.stats.device_dispatches += 1
                self.state[li] = new_state
                flops, byts = self._layer_cost(kind, x.shape, cur_len, hits=0)
                clock.compute(self.cost.compute_s(flops, byts), needs_dma=False)
        return x

    # ------------------------------------------------------------------
    # device-resident decode hot path
    # ------------------------------------------------------------------
    def _decode_step_hot(self, tok: np.ndarray) -> np.ndarray:
        """One decode step with a single queue-draining device->host pull.

        Returns host logits [B, V]. See the module docstring for the design.
        """
        cur_len = self.cur_len
        cur = jnp.int32(cur_len)
        x = self._embed(jnp.asarray(tok)[:, None])
        states_before = list(self.state)
        x_ins: List[jax.Array] = []                         # per-layer input refs
        snaps: Dict[int, Tuple[Any, jax.Array, int]] = {}   # li -> (slots, lut, moved)
        pend: List[Tuple[int, int, np.ndarray, np.ndarray, jax.Array]] = []
        order: List[Tuple] = []                             # modeled-clock ops
        for li, (kind, p_l) in enumerate(self.layers):
            x_ins.append(x)
            state = self.state[li]
            if kind == "attn_moe":
                moe_li = self.moe_index[li]
                attn_half, moe_half = self._block_fn(kind, "decode", routed=True)
                x_mid, h2, ids_dev, w_dev, new_state = attn_half(p_l, x, state, cur)
                slots_tree = self.manager.stores[moe_li].as_pytree()
                lut_dev = self.manager.device_lut(moe_li)
                x, miss_dev = moe_half(p_l, x_mid, h2, ids_dev, w_dev, slots_tree, lut_dev)
                self.stats.device_dispatches += 2
                self.state[li] = new_state
                # async D2H copies: by the time the host consumes these, the
                # MoE half + next layer's slot uploads are already queued, so
                # the reads overlap device work instead of draining the queue
                for a in (h2, ids_dev, w_dev, miss_dev):
                    a.copy_to_host_async()
                ids = np.asarray(ids_dev)
                weights = np.asarray(w_dev)
                h2_np = np.asarray(h2, np.float32).reshape(ids.shape[0], -1)
                self.stats.overlapped_pulls += 4
                # --- pre-gate next layer + predictor feedback (seed order) --
                nxt = (moe_li + 1) % self.num_moe_layers
                demand = self.predictor.predict(nxt, h2_np)
                moved = self.manager.prepare_layer(nxt, demand, clock=None)
                self.predictor.observe(moe_li, ids, weights)
                snaps[li] = (slots_tree, lut_dev, moved)
                pend.append((li, moe_li, ids, weights, miss_dev))
                order.append(("moe", li, moe_li, x.shape, moved))
            else:
                (block,) = self._block_fn(kind, "decode")
                x, new_state = block(p_l, x, state if state else {}, cur)
                self.stats.device_dispatches += 1
                self.state[li] = new_state
                order.append(("plain", li, kind, x.shape))
        logits_dev = self._lm_head(x[:, -1:])[:, 0]
        logits = np.asarray(logits_dev)        # THE one queue-draining pull
        self.stats.sync_pulls += 1
        miss_by_li = {li: np.asarray(md) for (li, _, _, _, md) in pend}
        missed = [li for (li, _, _, _, _) in pend if miss_by_li[li].any()]
        start = (
            missed[0]
            if (missed and self.rescfg.host_compute_misses)
            else len(self.layers)
        )
        # account stats + modeled clock for the (authoritative) prefix in the
        # same sequence the sync walk would have used; layers before the first
        # miss are exact as computed, so only the suffix needs replay
        for (li, moe_li, ids, _, _) in pend:
            if li >= start:
                break
            self.manager.record_routing(moe_li, ids, miss_by_li[li])
        for op in order:
            if op[1] >= start:
                break
            if op[0] == "moe":
                _, li, moe_li, shape, moved = op
                hits = int((~miss_by_li[li]).sum())
                flops, byts = self._layer_cost("attn_moe", shape, cur_len, hits=hits)
                self.clock.compute(self.cost.compute_s(flops, byts))
                self.clock.prefetch(moved)
            else:
                _, li, kind, shape = op
                flops, byts = self._layer_cost(kind, shape, cur_len, hits=0)
                self.clock.compute(self.cost.compute_s(flops, byts), needs_dma=False)
        if start < len(self.layers):
            return self._replay_step(x_ins[start], states_before, snaps, start)
        return logits

    def _replay_step(
        self,
        x0: jax.Array,
        states_before: List[Any],
        snaps: Dict[int, Tuple[Any, jax.Array, int]],
        start: int,
    ) -> np.ndarray:
        """Exact re-execution of a decode-step SUFFIX after an observed miss.

        Layers before ``start`` (the first layer whose optimistic pass missed)
        saw exactly the inputs/residency the sync walk would have used, so
        their optimistic outputs and KV writes stand. From ``start`` on, the
        step re-executes with the per-layer residency SNAPSHOTS captured by
        the hot pass (the slot buffers / LUT each layer actually gathered
        from), re-deriving routing from the corrected activations and applying
        the host GEMM correction between layers exactly like the sync walk.
        Rotation / prefetch already happened in the hot pass and is not
        repeated; its modeled DMA time is charged here at the seed position in
        the sequence.
        """
        cur_len = self.cur_len
        cur = jnp.int32(cur_len)
        clock = self.clock
        x = x0
        for li in range(start, len(self.layers)):
            kind, p_l = self.layers[li]
            state = states_before[li]
            if kind == "attn_moe":
                moe_li = self.moe_index[li]
                attn_half, moe_half = self._block_fn(kind, "decode", routed=True)
                x_mid, h2, ids_dev, w_dev, new_state = attn_half(p_l, x, state, cur)
                self.state[li] = new_state
                slots_tree, lut_dev, moved = snaps[li]
                x, miss_dev = moe_half(p_l, x_mid, h2, ids_dev, w_dev, slots_tree, lut_dev)
                ids = np.asarray(ids_dev)
                weights = np.asarray(w_dev)
                miss = np.asarray(miss_dev)
                self.stats.sync_pulls += 1
                self.manager.record_routing(moe_li, ids, miss)
                if miss.any() and self.rescfg.host_compute_misses:
                    x = self._host_correct(x, moe_li, h2, ids, weights, miss)
                flops, byts = self._layer_cost(kind, x.shape, cur_len, hits=int((~miss).sum()))
                clock.compute(self.cost.compute_s(flops, byts))
                clock.prefetch(moved)
            else:
                (block,) = self._block_fn(kind, "decode")
                x, new_state = block(p_l, x, state if state else {}, cur)
                self.state[li] = new_state
                flops, byts = self._layer_cost(kind, x.shape, cur_len, hits=0)
                clock.compute(self.cost.compute_s(flops, byts), needs_dma=False)
        logits = np.asarray(self._lm_head(x[:, -1:])[:, 0])
        self.stats.sync_pulls += 1
        return logits

    # ------------------------------------------------------------------
    # fused whole-stack decode (ONE compiled step per token)
    # ------------------------------------------------------------------
    def _stack_state(self, flat: List[Any]) -> Any:
        """Per-layer state list -> the stacked pytree ``decode_model`` scans
        (tuple over segments of tuples over unit positions, leading dim =
        reps). One-time cost after prefill; decode then threads the stacked
        state through the donated fused step without ever re-stacking."""
        segs: List[Tuple] = []
        base = 0
        for unit, reps in self.cfg.segments:
            unit_states = []
            for pi in range(len(unit)):
                per_rep = [
                    flat[base + r * len(unit) + pi] or {} for r in range(reps)
                ]
                unit_states.append(
                    jax.tree.map(lambda *xs: jnp.stack(xs), *per_rep)
                )
            segs.append(tuple(unit_states))
            base += reps * len(unit)
        return tuple(segs)

    def _layer_state(self, li: int) -> Any:
        si, pi, r = self._layer_pos[li]
        return jax.tree.map(lambda a: a[r], self._dstate[si][pi])

    def _set_layer_state(self, li: int, new_state: Any) -> None:
        si, pi, r = self._layer_pos[li]
        segs = list(self._dstate)
        unit = list(segs[si])
        unit[pi] = jax.tree.map(
            lambda full, s: full.at[r].set(s), unit[pi], new_state
        )
        segs[si] = tuple(unit)
        self._dstate = tuple(segs)

    def _decode_step_fused(self, tok: np.ndarray) -> np.ndarray:
        """One decode step = ONE compiled program launch (plus the rotation's
        batched uploads). Returns host logits [B, V]; see module docstring."""
        cur_len = self.cur_len
        tr = self._tr
        if tr is not None:
            tr.new_unit("decode")
            t_trace = time.perf_counter()
        residency = self.manager.stacked_residency()
        logits_dev, self._dstate, aux = self._fused_step(
            self._decode_params, self._routers_next, jnp.asarray(tok),
            self._dstate, jnp.int32(cur_len), residency,
        )
        self.stats.device_dispatches += 1
        if tr is not None:
            tr.complete("launch", "launch", t_trace, time.perf_counter(),
                        args={"cur_len": cur_len})
        # async D2H: these complete while the logits pull below drains the
        # queue, so the rotation bookkeeping reads ready host buffers
        for k in self._pull_keys:
            aux[k].copy_to_host_async()
        self.stats.overlapped_pulls += len(self._pull_keys)
        if self.prefetch:
            # the launch above is still in flight: plan the predicted next
            # transition and ship its uploads into the SHADOW generation now,
            # so this host work + the scatters overlap the device compute the
            # blocking pull below waits on
            self.manager.begin_prefetch(self.predictor, self.clock)
        if tr is not None:
            t_trace = time.perf_counter()
        logits = np.asarray(logits_dev)        # THE one queue-draining pull
        self.stats.sync_pulls += 1
        if tr is not None:
            tr.complete("pull", "pull", t_trace, time.perf_counter(),
                        args={"cur_len": cur_len})
        ids = concat_route_telemetry(aux, "ids", self._moe_segs)      # [L, T, k]
        weights = concat_route_telemetry(aux, "weights", self._moe_segs)
        miss = concat_route_telemetry(aux, "miss", self._moe_segs)
        demand_next = np.asarray(aux["demand_next"])   # [L, E]
        missed = np.flatnonzero(miss.reshape(miss.shape[0], -1).any(axis=1))
        if tr is not None and missed.size:
            tr.instant("miss", "launch",
                       args={"first_moe": int(missed[0]),
                             "layers": int(missed.size)})
        start_moe = (
            int(missed[0])
            if (missed.size and self.rescfg.host_compute_misses)
            else self.num_moe_layers
        )
        start_li = (
            self._moe_layer_li[start_moe]
            if start_moe < self.num_moe_layers
            else len(self.layers)
        )
        # stats + modeled clock for the authoritative prefix in seed order
        # (layers before the first miss are exact as computed; the replay
        # charges the suffix itself)
        self._account_step_prefix(ids, miss, start_li, cur_len)
        if start_li < len(self.layers):
            # miss-relaunch (prefetch mode): upload the known-missed experts —
            # no prediction involved — let the incremental planes/LUT absorb
            # the patch off the shared generation counter, and re-run the ONE
            # compiled step. Far cheaper than the per-layer replay walk with
            # its sync pull per MoE layer; falls back to the replay when the
            # residency cannot cover the routed set.
            redo = (
                self._relaunch_fused(tok, cur_len, ids, start_moe, start_li)
                if self.prefetch else None
            )
            if redo is not None:
                logits, ids, weights, miss, demand_next = redo
            else:
                logits = self._replay_fused(aux, start_moe, start_li, cur_len)
        # between-step rotation: the pre-gating GEMM already ran on device;
        # host work is the EMA fold, the ring transition, and ONE batched
        # (donated) scatter per weight tensor per rotated layer
        self.manager.rotate_from_telemetry(
            self.predictor, ids, weights, miss, demand_next,
            clock=self.clock, record=False,
        )
        return logits

    def _account_step_prefix(
        self,
        ids: np.ndarray,
        miss: np.ndarray,
        stop_li: int,
        cur_len: int,
        tokens: int = 1,
        start_li: int = 0,
    ) -> None:
        """record_routing + modeled clock for layers ``[start_li, stop_li)`` of
        one authoritative step (ids/miss [L, T, k]), in seed order — shared by
        the fused decode step, every position of a speculative window, each
        fused prefill chunk (``tokens`` = positions the launch processed), and
        the miss-relaunch suffix."""
        xshape = (self.batch, tokens, self.cfg.d_model)
        for li, (kind, _) in enumerate(self.layers):
            if li >= stop_li:
                break
            if li < start_li:
                continue
            moe_li = self.moe_index[li]
            if moe_li is not None:
                self.manager.record_routing(moe_li, ids[moe_li], miss[moe_li])
                hits = int((~miss[moe_li]).sum())
                flops, byts = self._layer_cost(kind, xshape, cur_len, hits=hits)
                self.clock.compute(self.cost.compute_s(flops, byts))
            else:
                flops, byts = self._layer_cost(kind, xshape, cur_len, hits=0)
                self.clock.compute(self.cost.compute_s(flops, byts), needs_dma=False)

    # ------------------------------------------------------------------
    # speculative multi-token decode (ONE compiled window per K tokens)
    # ------------------------------------------------------------------
    def _window_fns(
        self, k: int, sample: Optional[SampleParams] = None
    ) -> Tuple[Callable, Callable, Callable]:
        """Compiled (window step, KV snapshot, KV rollback) triple for window
        size ``k`` (cached per (k, sample) — decode tails may need a smaller
        final window, and sampled windows are a distinct compiled family)."""
        fns = self._fused_windows.get((k, sample))
        if fns is None:
            fns = build_window_fns(
                self.cfg, self.rt, k, with_demand=True, sample=sample
            )
            self._fused_windows[(k, sample)] = fns
        return fns

    def _decode_window_fused(
        self, tok: np.ndarray, k: int,
        sample: Optional[SampleParams] = None,
        rng_keys: Optional[jax.Array] = None,
        sample_rng: Optional[np.random.Generator] = None,
    ) -> Tuple[np.ndarray, np.ndarray, int]:
        """One speculative window: ``k`` self-drafted positions through
        ONE compiled program, one queue-draining pull, acceptance by the
        accept rule + miss telemetry, KV rollback + suffix replay for the
        first rejected position, rotation at the window boundary.

        ``tok`` [B] is the position-0 token (already emitted by the caller).
        Returns ``(extra [committed-1, B], logits [B, V], committed)``:
        ``extra`` are the drafted tokens that committed beyond ``tok``, and
        ``logits`` continue the chain (the last committed position's —
        replay-corrected when that position missed). Exactness: positions
        before the first miss saw exactly the inputs and residency the
        single-token fused path would have used (the window defers rotation
        to its boundary, and a miss-free step's rotation cannot change its
        own output — only WHERE later steps' compute happens, which the
        replay machinery already corrects), so committed tokens are
        bit-identical to single-token decode.

        With ``sample``/``rng_keys`` the window drafts by on-device
        position-keyed draws and acceptance runs
        :func:`repro.serving.sampler.stochastic_accept` over the pulled
        ``sample_probs`` telemetry. Self-drafting passes the same
        distributions as p and q, so the stochastic rule accepts every
        position (its resample path is dormant — a rejected-suffix re-draw
        happens at the caller's loop top with the SAME position key, which
        is the exact q-draw) and rejection still comes only from residency
        misses; sampled committed tokens are bit-identical to single-token
        sampled decode under the shared PRNG protocol.
        """
        cur_len0 = self.cur_len
        tr = self._tr
        if tr is not None:
            tr.new_unit("window")
        residency = self.manager.stacked_residency()
        step_fn, snap_fn, roll_fn = self._window_fns(k, sample)
        saved = None
        if self._spec_needs_rollback:
            # gather the pre-window contents of the K slots the window will
            # write, BEFORE the window donates (and mutates) the state
            saved = snap_fn(self._dstate, jnp.int32(cur_len0))
            self.stats.device_dispatches += 1
            if tr is not None:
                tr.instant("kv_snapshot", "launch", args={"k": k})
        if tr is not None:
            t_trace = time.perf_counter()
        draft_dev, logits_dev, self._dstate, aux = step_fn(
            self._decode_params, self._routers_next, jnp.asarray(tok),
            self._dstate, jnp.int32(cur_len0), residency,
            rng_keys=rng_keys,
        )
        self.stats.device_dispatches += 1
        self.stats.spec_windows += 1
        if tr is not None:
            tr.complete("launch", "launch", t_trace, time.perf_counter(),
                        args={"cur_len": cur_len0, "k": k})
        pull_keys = self._pull_keys
        if sample is not None:
            pull_keys = pull_keys + ["sample_probs", "sample_p"]
        for key in pull_keys:
            aux[key].copy_to_host_async()
        draft_dev.copy_to_host_async()
        self.stats.overlapped_pulls += len(pull_keys) + 1
        if self.prefetch:
            # whole window still in flight: shadow-upload the predicted next
            # transition under it (committed at the boundary rotation below)
            self.manager.begin_prefetch(self.predictor, self.clock)
        if tr is not None:
            t_trace = time.perf_counter()
        logits = np.asarray(logits_dev)        # THE one queue-draining pull
        self.stats.sync_pulls += 1
        if tr is not None:
            tr.complete("pull", "pull", t_trace, time.perf_counter(),
                        args={"cur_len": cur_len0, "k": k})
        draft = np.asarray(draft_dev)                               # [K, B]
        ids = concat_route_telemetry(aux, "ids", self._moe_segs, axis=1)
        weights = concat_route_telemetry(aux, "weights", self._moe_segs, axis=1)
        miss = concat_route_telemetry(aux, "miss", self._moe_segs, axis=1)
        demand_next = np.asarray(aux["demand_next"])                # [K, L, E]
        # --- accept rule ------------------------------------------------
        # self-draft with identical weights: greedy verification argmaxes ARE
        # the drafted tokens, and the stochastic rule sees draft dist ==
        # verify dist (ratio exactly 1 -> certain acceptance) — so either way
        # the token-level rule accepts everything (the call is the plug point
        # for a separate drafter) and rejection comes only from residency
        # misses invalidating a position and everything drafted after it
        from repro.serving.sampler import greedy_accept, stochastic_accept

        if sample is None:
            accept = int(greedy_accept(draft, draft).min())
        else:
            probs = np.asarray(aux["sample_probs"])             # [K, B, V]
            s_acc, _ = stochastic_accept(draft, probs, probs, sample_rng)
            accept = int(s_acc.min())
        miss_steps = miss.reshape(k, -1).any(axis=1)                # [K]
        missed = np.flatnonzero(miss_steps)
        if tr is not None and missed.size:
            tr.instant("miss", "launch",
                       args={"first_step": int(missed[0]),
                             "steps": int(missed.size)})
        j_star = None
        if missed.size and self.rescfg.host_compute_misses:
            j_star = int(missed[0])
            accept = min(accept, j_star)
        if j_star is not None and self.prefetch:
            # miss-relaunch for the whole window: cover every layer's routed
            # union across the K positions and re-run the ONE compiled window
            # program (it rewrites all K KV slots itself, so no rollback is
            # needed on success). Positions before the first miss recompute
            # bit-identically; the rest become the exact corrected chain —
            # the whole window commits instead of rejecting the suffix.
            redo = self._relaunch_window(
                step_fn, tok, cur_len0, k, ids,
                sample=sample, rng_keys=rng_keys,
            )
            if redo is not None:
                draft, logits, ids, weights, miss, demand_next, probs = redo
                if sample is None:
                    accept = int(greedy_accept(draft, draft).min())
                else:
                    s_acc, _ = stochastic_accept(
                        draft, probs, probs, sample_rng
                    )
                    accept = int(s_acc.min())
                j_star = None
        self.stats.drafted_tokens += k
        self.stats.accepted_tokens += accept
        # --- stats + modeled clock for fully-accepted positions ---------
        for s in range(accept):
            self._account_step_prefix(
                ids[s], miss[s], len(self.layers), cur_len0 + s
            )
        committed = accept
        if j_star is not None:
            # reject the suffix: roll the KV cache back past position j*
            # (restore the pre-window slot contents the rejected positions
            # overwrote — ``tfm.rollback_kv_window``), then replay position
            # j* from its first missed layer exactly like a missed
            # single-token step
            miss_j = miss[j_star]
            start_moe = int(
                np.flatnonzero(
                    miss_j.reshape(miss_j.shape[0], -1).any(axis=1)
                )[0]
            )
            start_li = self._moe_layer_li[start_moe]
            self._dstate = roll_fn(
                self._dstate, saved, jnp.int32(cur_len0), jnp.int32(j_star + 1)
            )
            self.stats.device_dispatches += 1
            if tr is not None:
                tr.instant("kv_rollback", "launch", args={"j_star": j_star})
            self._account_step_prefix(
                ids[j_star], miss[j_star], start_li, cur_len0 + j_star
            )
            logits = self._replay_fused(
                aux, start_moe, start_li, cur_len0 + j_star, step=j_star
            )
            committed = j_star + 1
        # --- window-boundary rotation from committed telemetry ----------
        # host-side transitions run per committed step (residency evolves
        # exactly as one-token-at-a-time); uploads + LUT patches amortize to
        # one batched dispatch per layer per window
        self.manager.rotate_window_from_telemetry(
            self.predictor, ids[:committed], weights[:committed],
            miss[:committed], demand_next[:committed],
            clock=self.clock, record=False,
        )
        return draft[: committed - 1], logits, committed

    def _relaunch_fused(
        self,
        tok: np.ndarray,
        cur_len: int,
        ids0: np.ndarray,
        start_moe: int,
        start_li: int,
    ) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]]:
        """Miss correction by RE-LAUNCH (prefetch mode): the telemetry names
        the missed experts exactly, so upload them, patch the persistent
        planes/LUT incrementally, and re-run the whole compiled step at the
        SAME ``cur_len`` — the relaunch overwrites every KV slot the optimistic
        pass wrote, and a miss-free launch is bit-identical to the
        host-corrected replay (the full-vs-starved exactness invariant), so
        greedy tokens cannot move. One compiled launch + one pull replaces the
        per-layer suffix walk and its sync pull per MoE layer.

        Corrected routing can route to NEW experts (the suffix recomputes from
        corrected hiddens); one more covering relaunch is allowed before
        falling back. Returns ``(logits, ids, weights, miss, demand_next)``
        from the authoritative miss-free pass, or None when residency cannot
        cover a layer's routed set (caller replays) or misses persist."""
        ids_cur = ids0
        for _ in range(2):
            # feasibility first, BEFORE paying any upload: ensure_resident can
            # cover layer l iff |unique routed| <= num_slots (every occupant is
            # either routed — it stays — or evictable), so a doomed relaunch
            # costs nothing and falls straight back to the replay
            routed_all = [
                np.unique(ids_cur[m]) for m in range(start_moe, self.num_moe_layers)
            ]
            if any(
                r.size > self.manager.policies[start_moe + i].lut.num_slots
                for i, r in enumerate(routed_all)
            ):
                return None
            moved = 0
            for i, moe_li in enumerate(range(start_moe, self.num_moe_layers)):
                routed = routed_all[i]
                loads = self.manager.ensure_resident(moe_li, routed, routed)
                if loads is None:
                    return None
                moved += len(loads) * self.manager.stores[moe_li].bytes_per_expert
            if moved:
                self.clock.blocking(moved)
            tr = self._tr
            if tr is not None:
                t_trace = time.perf_counter()
            residency = self.manager.stacked_residency()
            logits_dev, self._dstate, aux = self._fused_step(
                self._decode_params, self._routers_next, jnp.asarray(tok),
                self._dstate, jnp.int32(cur_len), residency,
            )
            self.stats.device_dispatches += 1
            self.stats.relaunched_steps += 1
            if tr is not None:
                tr.complete("launch", "launch", t_trace, time.perf_counter(),
                            args={"kind": "relaunch"})
            for k in self._pull_keys:
                aux[k].copy_to_host_async()
            if tr is not None:
                t_trace = time.perf_counter()
            logits = np.asarray(logits_dev)
            self.stats.sync_pulls += 1
            if tr is not None:
                tr.complete("pull", "pull", t_trace, time.perf_counter(),
                            args={"kind": "relaunch"})
            ids = concat_route_telemetry(aux, "ids", self._moe_segs)
            weights = concat_route_telemetry(aux, "weights", self._moe_segs)
            miss = concat_route_telemetry(aux, "miss", self._moe_segs)
            demand_next = np.asarray(aux["demand_next"])
            if not miss.any():
                # suffix accounting: the caller charged layers < start_li from
                # the original launch; the relaunch is authoritative for the
                # rest (exactly the slice _replay_fused would have recorded)
                self._account_step_prefix(
                    ids, miss, len(self.layers), cur_len, start_li=start_li
                )
                return logits, ids, weights, miss, demand_next
            ids_cur = ids
        return None

    def _relaunch_window(
        self,
        step_fn: Callable,
        tok: np.ndarray,
        cur_len0: int,
        k: int,
        ids0: np.ndarray,
        sample: Optional[SampleParams] = None,
        rng_keys: Optional[jax.Array] = None,
    ) -> Optional[Tuple[np.ndarray, ...]]:
        """Window-sized miss relaunch: cover each layer's routed-expert union
        across all K positions (None when it exceeds the slot count — spec
        windows can route wider than a single step) and re-run the compiled
        window program. On success every position is exact, so the caller
        commits all K tokens; on persistent misses the caller falls back to
        the classic rollback + suffix replay against the ORIGINAL telemetry,
        which stays valid because positions before the first miss recompute
        bit-identically and the pre-window KV snapshot is untouched. Sampled
        windows relaunch with the SAME ``rng_keys`` — position keys are a
        pure function of cache position, so the corrected chain re-draws
        deterministically — and return the relaunched ``sample_probs`` (the
        trailing tuple slot, None for greedy) for the caller's re-run of the
        stochastic accept rule."""
        ids_cur = ids0                                     # [K, L, T, kk]
        for _ in range(2):
            # same zero-cost feasibility gate as the single-step relaunch —
            # crucial here, because a window's routed union across K positions
            # regularly exceeds the slot count and the fallback replay would
            # otherwise be paid ON TOP of wasted uploads and a wasted launch
            routed_all = [
                np.unique(ids_cur[:, m]) for m in range(self.num_moe_layers)
            ]
            if any(
                r.size > self.manager.policies[m].lut.num_slots
                for m, r in enumerate(routed_all)
            ):
                return None
            moved = 0
            for moe_li in range(self.num_moe_layers):
                routed = routed_all[moe_li]
                loads = self.manager.ensure_resident(moe_li, routed, routed)
                if loads is None:
                    return None
                moved += len(loads) * self.manager.stores[moe_li].bytes_per_expert
            if moved:
                self.clock.blocking(moved)
            tr = self._tr
            if tr is not None:
                t_trace = time.perf_counter()
            residency = self.manager.stacked_residency()
            draft_dev, logits_dev, self._dstate, aux = step_fn(
                self._decode_params, self._routers_next, jnp.asarray(tok),
                self._dstate, jnp.int32(cur_len0), residency,
                rng_keys=rng_keys,
            )
            self.stats.device_dispatches += 1
            self.stats.relaunched_steps += 1
            if tr is not None:
                tr.complete("launch", "launch", t_trace, time.perf_counter(),
                            args={"kind": "relaunch"})
            pull_keys = self._pull_keys
            if sample is not None:
                pull_keys = pull_keys + ["sample_probs", "sample_p"]
            for key in pull_keys:
                aux[key].copy_to_host_async()
            draft_dev.copy_to_host_async()
            if tr is not None:
                t_trace = time.perf_counter()
            logits = np.asarray(logits_dev)
            self.stats.sync_pulls += 1
            if tr is not None:
                tr.complete("pull", "pull", t_trace, time.perf_counter(),
                            args={"kind": "relaunch"})
            draft = np.asarray(draft_dev)
            ids = concat_route_telemetry(aux, "ids", self._moe_segs, axis=1)
            weights = concat_route_telemetry(aux, "weights", self._moe_segs, axis=1)
            miss = concat_route_telemetry(aux, "miss", self._moe_segs, axis=1)
            demand_next = np.asarray(aux["demand_next"])
            if not miss.any():
                probs = (
                    np.asarray(aux["sample_probs"]) if sample is not None
                    else None
                )
                return draft, logits, ids, weights, miss, demand_next, probs
            ids_cur = ids
        return None

    def _replay_fused(
        self,
        aux: Dict[str, jax.Array],
        start_moe: int,
        start_li: int,
        cur_len: int,
        step: Optional[int] = None,
    ) -> np.ndarray:
        """Exact re-execution of a fused-step SUFFIX after an observed miss.

        Same contract as ``_replay_step``: layers before ``start_li`` saw
        exactly the inputs/residency the sync walk would have used, so their
        outputs and KV writes stand. The suffix re-executes with the per-layer
        walk from the fused pass's saved block input (``route_x`` telemetry)
        against the SAME residency the compiled step gathered from — rotation
        runs strictly after this replay. Re-running an attention block
        overwrites the very KV slot the optimistic pass wrote, so the
        post-step donated state is a valid replay substrate.

        ``step`` indexes a speculative window's leading K axis (the rejected
        position being replayed at ``cur_len``); the window path rolls the KV
        cache back past ``step`` BEFORE calling this, so the cache the suffix
        reads holds no writes from rejected positions.
        """
        tr = self._tr
        t_trace = time.perf_counter() if tr is not None else 0.0
        si0, r0 = self._moe_pos[start_moe]
        x_anchor = aux[f"route_x/seg{si0}"]
        if step is not None:
            x_anchor = x_anchor[step]
        x = x_anchor[r0].reshape(self.batch, 1, -1)
        self.stats.device_dispatches += 1             # device-side slice
        cur = jnp.int32(cur_len)
        clock = self.clock
        for li in range(start_li, len(self.layers)):
            kind, p_l = self.layers[li]
            state = self._layer_state(li)
            if kind == "attn_moe":
                moe_li = self.moe_index[li]
                attn_half, moe_half = self._block_fn(kind, "decode", routed=True)
                x_mid, h2, ids_dev, w_dev, new_state = attn_half(p_l, x, state, cur)
                slots_tree = self.manager.stores[moe_li].as_pytree()
                lut_dev = self.manager.device_lut(moe_li)
                x, miss_dev = moe_half(
                    p_l, x_mid, h2, ids_dev, w_dev, slots_tree, lut_dev
                )
                self.stats.device_dispatches += 2
                ids = np.asarray(ids_dev)
                weights = np.asarray(w_dev)
                miss = np.asarray(miss_dev)
                self.stats.sync_pulls += 1
                self.stats.replay_pulls += 1
                self.manager.record_routing(moe_li, ids, miss)
                if miss.any() and self.rescfg.host_compute_misses:
                    x = self._host_correct(x, moe_li, h2, ids, weights, miss)
                flops, byts = self._layer_cost(
                    kind, x.shape, cur_len, hits=int((~miss).sum())
                )
                clock.compute(self.cost.compute_s(flops, byts))
            else:
                (block,) = self._block_fn(kind, "decode")
                x, new_state = block(p_l, x, state if state else {}, cur)
                self.stats.device_dispatches += 1
                flops, byts = self._layer_cost(kind, x.shape, cur_len, hits=0)
                clock.compute(self.cost.compute_s(flops, byts), needs_dma=False)
            self._set_layer_state(li, new_state)
        logits = np.asarray(self._lm_head(x[:, -1:])[:, 0])
        self.stats.sync_pulls += 1
        self.stats.replay_pulls += 1
        self.stats.replayed_steps += 1
        if tr is not None:
            tr.complete("replay", "launch", t_trace, time.perf_counter(),
                        args={"start_li": start_li, "step": step})
        return logits

    def _layer_cost(self, kind: str, xshape, cur_len: int, hits: int) -> Tuple[float, float]:
        """(flops, bytes) estimate of one layer at current shapes (modeled clock).

        The per-kind static parameter counts are computed once and cached —
        this runs per layer per decode step on the host and must stay off the
        critical path.
        """
        cfg = self.cfg
        cached = self._cost_cache.get(kind)
        if cached is None:
            from repro.models.params import _block_params

            n_static = float(_block_params(cfg, kind, active_only=True))
            per_hit = 0.0
            if kind == "attn_moe":
                m = cfg.moe
                mats = 3 if cfg.mlp == "swiglu" else 2
                n_static -= m.top_k * mats * cfg.d_model * m.expert_d_ff
                per_hit = float(mats * cfg.d_model * m.expert_d_ff)
            cached = (n_static, per_hit)
            self._cost_cache[kind] = cached
        n_static, per_hit = cached
        tokens = int(np.prod(xshape[:-1]))
        flops = 2.0 * tokens * n_static + 2.0 * hits * per_hit
        byts = 2.0 * n_static + 2.0 * hits * per_hit
        if cfg.uses_kv_cache and kind in ("attn_mlp", "attn_moe", "local_attn"):
            a = cfg.attention
            ctx = min(cur_len + 1, self.rt.cache_len)
            if kind == "local_attn" and a.window:
                ctx = min(ctx, a.window)
            flops += 4.0 * tokens * ctx * a.num_heads * a.head_dim
            byts += 2.0 * xshape[0] * ctx * a.num_kv_heads * a.head_dim * 2
        return flops, byts

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def prefill(self, tokens: np.ndarray) -> np.ndarray:
        """tokens [B, S] -> logits [B, V]; builds the decode state.

        With ``prefill_chunk=C`` (KV-only stacks) the prompt ingests in
        power-of-two chunks: fused engines launch ONE compiled program per
        chunk with one coalesced rotation window between chunks (misses
        suffix-replayed per chunk, exactly like decode); per-layer engines
        walk the same chunks layer-by-layer. Logits and post-prefill KV are
        bit-identical BETWEEN the two chunked paths (fused vs walk, any
        residency mode or slot format), and greedy continuations match the
        legacy full-sequence walk token for token. Prompts longer than the
        KV capacity fall back to the legacy walk: chunk appends would wrap
        the cache ring mid-prompt, silently corrupting attention, where the
        legacy path at least attends over the full prompt before truncating.
        """
        b, s = tokens.shape
        assert b == self.batch
        from repro.models.attention import _cache_capacity

        chunked = (
            self.prefill_chunk is not None
            and self._chunk_prefill_ok
            and s <= _cache_capacity(self.cfg.attention, self.rt.cache_len)
        )
        t0 = time.perf_counter()
        if chunked and self._fused_decode and self._chunk_prefill_fused_ok:
            logits = self._prefill_fused_chunked(tokens)
            self.state = None
        else:
            self.state = [
                tfm._zero_block_state(self.cfg, kind, b, self.rt.cache_len)
                for kind, _ in self.layers
            ]
            if chunked:
                logits = self._prefill_walk_chunked(tokens)
            else:
                x = self._embed(jnp.asarray(tokens))
                x = self._run_layers(x, "prefill", cur_len=0)
                logits = self._lm_head(x[:, -1:])[:, 0]
            if self._fused_decode:
                # one-time: stack the per-layer states into the scan layout
                # the fused step consumes (and donates back, updated in place)
                self._dstate = self._stack_state(self.state)
                self.state = None
        self.stats.wall_s += time.perf_counter() - t0
        self.cur_len = s
        self.stats.tokens += b * s
        return np.asarray(logits)

    def _rotate_chunk_boundary(
        self,
        ids: np.ndarray,                 # [L, T, k] the chunk's routing
        weights: np.ndarray,             # [L, T, k]
        miss: np.ndarray,                # [L, T, k]
        h_all: Optional[jax.Array] = None,   # [L, T, D] stacked MoE hiddens
        demand_dev: Optional[jax.Array] = None,  # pre-dispatched GEMM result
    ) -> None:
        """ONE coalesced rotation window at a chunk boundary, shared by the
        walk and fused chunked prefill paths: the pre-gating demand GEMM runs
        on device over the stacked per-layer hiddens (``_demand_all_jit`` —
        the same compiled program in both paths, so residency evolves
        bit-identically), then ``rotate_from_telemetry`` folds the EMA, runs
        each layer's ring transition once, and batches the uploads to one
        scatter per weight tensor per rotated layer. The fused path dispatches
        the GEMM under the still-in-flight chunk launch and passes the result
        as ``demand_dev``. Hit/miss accounting already happened (walk:
        ``resolve``; fused: prefix accounting + replay), hence
        ``record=False``."""
        if demand_dev is None:
            demand_dev = self._demand_all_jit(h_all, self._routers_next)
            self.stats.device_dispatches += 1
        demand = np.asarray(demand_dev)
        self.manager.rotate_from_telemetry(
            self.predictor, ids, weights, miss, demand,
            clock=self.clock, record=False,
        )

    def _prefill_walk_chunked(self, tokens: np.ndarray) -> jax.Array:
        """Per-layer chunked prefill (the layer-walk baseline, and the chunked
        path for host_routing / LRU / ``fused_decode=False`` engines): each
        chunk walks the stack with the same chunk-append attention the fused
        step uses — one host sync per MoE layer per chunk — then rotates once
        at the chunk boundary."""
        s = tokens.shape[1]
        d = self.cfg.d_model
        cur, x = 0, None
        for c in prefill_chunk_plan(s, self.prefill_chunk):
            self._chunk_telem = []
            x = self._embed(jnp.asarray(tokens[:, cur : cur + c]))
            x = self._run_layers(x, "chunk", cur_len=cur)
            self.stats.prefill_chunks += 1
            self._rotate_chunk_boundary(
                np.stack([t[0] for t in self._chunk_telem]),
                np.stack([t[1] for t in self._chunk_telem]),
                np.stack([t[2] for t in self._chunk_telem]),
                jnp.stack([t[3].reshape(-1, d) for t in self._chunk_telem]),
            )
            cur += c
        self._chunk_telem = []      # don't pin the last chunk's device hiddens
        return self._lm_head(x[:, -1:])[:, 0]

    def _prefill_fused_chunked(self, tokens: np.ndarray) -> np.ndarray:
        """Fused chunked prefill: ONE compiled whole-stack launch + one
        queue-draining pull + one coalesced rotation window per chunk.

        Per chunk: (1) launch the fused prefill-chunk step against the
        current ``stacked_residency()`` with donated KV; (2) exactness — if
        the optimistic pass missed, the chunk suffix replays from the first
        missed layer with the per-layer walk (``_replay_prefill_chunk``),
        host-correcting exactly like the walk baseline and patching the
        telemetry with the authoritative routing/hiddens; (3) rotate once at
        the boundary (``_rotate_chunk_boundary``: shared demand GEMM + EMA
        fold + ring transitions + batched uploads). The final chunk also
        rotates, so decode starts pre-gated the same way the walk leaves it.
        """
        b, s = tokens.shape
        self._dstate = tfm.zero_state(self.cfg, b, self.rt.cache_len)
        plan = prefill_chunk_plan(s, self.prefill_chunk)
        cur, logits = 0, None
        tr = self._tr
        for ci, c in enumerate(plan):
            last = ci == len(plan) - 1
            step_fn = (
                self._fused_prefill_step if last
                else self._fused_prefill_step_nohead
            )
            if tr is not None:
                tr.new_unit("chunk")
                t_trace = time.perf_counter()
            residency = self.manager.stacked_residency()
            logits_dev, self._dstate, aux = step_fn(
                self._decode_params, self._routers_next,
                jnp.asarray(tokens[:, cur : cur + c]), self._dstate,
                jnp.int32(cur), residency,
            )
            self.stats.device_dispatches += 1
            self.stats.prefill_chunks += 1
            if tr is not None:
                tr.complete("launch", "launch", t_trace, time.perf_counter(),
                            args={"chunk": c, "cur_len": cur})
            for k in self._prefill_pull_keys:
                aux[k].copy_to_host_async()
            self.stats.overlapped_pulls += len(self._prefill_pull_keys)
            # dispatch the boundary demand GEMM behind the in-flight launch:
            # its input is the step's own route_h output, so it is computed
            # by the time the blocking telemetry pulls below drain the queue
            # (only usable when no replay patches the hiddens — see below)
            segs = [aux[f"route_h/seg{si}"] for si in self._moe_segs]
            h_fast = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
            demand_dev = self._demand_all_jit(h_fast, self._routers_next)
            self.stats.device_dispatches += 1
            if self.prefetch:
                # chunk launch in flight: shadow-upload the predicted next
                # chunk-boundary transition under it
                self.manager.begin_prefetch(self.predictor, self.clock)
            if tr is not None:
                t_trace = time.perf_counter()
            if last:
                logits = np.asarray(logits_dev)  # THE queue-draining pull
            self.stats.sync_pulls += 1
            # non-final chunks have no head output: the first telemetry read
            # below is their one queue-draining pull instead
            ids = concat_route_telemetry(aux, "ids", self._moe_segs)  # [L,T,k]
            if tr is not None:
                tr.complete("pull", "pull", t_trace, time.perf_counter(),
                            args={"chunk": c})
            weights = concat_route_telemetry(aux, "weights", self._moe_segs)
            miss = concat_route_telemetry(aux, "miss", self._moe_segs)
            missed = np.flatnonzero(miss.reshape(miss.shape[0], -1).any(axis=1))
            if tr is not None and missed.size:
                tr.instant("miss", "launch",
                           args={"first_moe": int(missed[0]),
                                 "layers": int(missed.size)})
            start_moe = (
                int(missed[0])
                if (missed.size and self.rescfg.host_compute_misses)
                else self.num_moe_layers
            )
            start_li = (
                self._moe_layer_li[start_moe]
                if start_moe < self.num_moe_layers
                else len(self.layers)
            )
            self._account_step_prefix(ids, miss, start_li, cur, tokens=c)
            if start_li < len(self.layers):
                # the replay patches authoritative rows in place; telemetry
                # views of device buffers are read-only, so copy first
                ids, weights, miss = (
                    np.array(a) for a in (ids, weights, miss)
                )
                h_rows = [
                    aux[f"route_h/seg{si}"][r]
                    for si, r in self._moe_pos
                ]                               # per MoE layer: [T, D] device
                replay_logits = self._replay_prefill_chunk(
                    aux, start_moe, start_li, cur, c,
                    ids, weights, miss, h_rows, with_head=last,
                )
                if last:
                    logits = replay_logits
                # the replay patched the hiddens — the optimistic GEMM read
                # stale rows; re-run it over the authoritative stack
                self._rotate_chunk_boundary(
                    ids, weights, miss, h_all=jnp.stack(h_rows)
                )
            else:
                self._rotate_chunk_boundary(
                    ids, weights, miss, demand_dev=demand_dev
                )
            cur += c
        return logits

    def _replay_prefill_chunk(
        self,
        aux: Dict[str, jax.Array],
        start_moe: int,
        start_li: int,
        cur_len: int,
        chunk: int,
        ids_all: np.ndarray,             # [L, T, k] — patched in place
        weights_all: np.ndarray,
        miss_all: np.ndarray,
        h_rows: List[jax.Array],         # per MoE layer [T, D] — patched too
        with_head: bool = True,
    ) -> Optional[np.ndarray]:
        """Exact re-execution of a prefill-chunk SUFFIX after an observed miss
        — :meth:`_replay_fused` at chunk width. Layers before ``start_li`` saw
        exactly what the layer walk would have computed, so their outputs and
        KV writes stand; the suffix re-runs per layer from the chunk's saved
        block input (``route_x`` [T, D] reshaped to [B, C, D]) against the
        same residency the launch gathered from, host-correcting between
        layers. Re-running a chunk's attention overwrites the very cache
        slots the optimistic pass wrote (window-free caches only — the fused
        gate), so the post-launch donated state is a valid replay substrate.

        The replayed layers' AUTHORITATIVE routing and hiddens are patched
        into the caller's telemetry arrays, so the boundary rotation consumes
        exactly what the walk baseline would have produced — residency after
        the chunk is bit-identical across paths. ``with_head=False`` (every
        chunk but the prompt's last) skips the lm-head GEMM and its logits
        pull — only the final chunk's logits are consumed.
        """
        tr = self._tr
        t_replay = time.perf_counter() if tr is not None else 0.0
        si0, r0 = self._moe_pos[start_moe]
        x = aux[f"route_x/seg{si0}"][r0].reshape(self.batch, chunk, -1)
        self.stats.device_dispatches += 1             # device-side slice
        cur = jnp.int32(cur_len)
        clock = self.clock
        for li in range(start_li, len(self.layers)):
            kind, p_l = self.layers[li]
            state = self._layer_state(li)
            if kind == "attn_moe":
                moe_li = self.moe_index[li]
                attn_half, moe_half = self._block_fn(kind, "chunk", routed=True)
                x_mid, h2, ids_dev, w_dev, new_state = attn_half(p_l, x, state, cur)
                slots_tree = self.manager.stores[moe_li].as_pytree()
                lut_dev = self.manager.device_lut(moe_li)
                x, miss_dev = moe_half(
                    p_l, x_mid, h2, ids_dev, w_dev, slots_tree, lut_dev
                )
                self.stats.device_dispatches += 2
                ids = np.asarray(ids_dev)
                weights = np.asarray(w_dev)
                miss = np.asarray(miss_dev)
                self.stats.sync_pulls += 1
                self.stats.replay_pulls += 1
                self.manager.record_routing(moe_li, ids, miss)
                if miss.any() and self.rescfg.host_compute_misses:
                    x = self._host_correct(x, moe_li, h2, ids, weights, miss)
                ids_all[moe_li] = ids
                weights_all[moe_li] = weights
                miss_all[moe_li] = miss
                h_rows[moe_li] = h2.reshape(-1, x.shape[-1])
                flops, byts = self._layer_cost(
                    kind, x.shape, cur_len, hits=int((~miss).sum())
                )
                clock.compute(self.cost.compute_s(flops, byts))
            else:
                (block,) = self._block_fn(kind, "chunk")
                x, new_state = block(p_l, x, state if state else {}, cur)
                self.stats.device_dispatches += 1
                flops, byts = self._layer_cost(kind, x.shape, cur_len, hits=0)
                clock.compute(self.cost.compute_s(flops, byts), needs_dma=False)
            self._set_layer_state(li, new_state)
        self.stats.prefill_replays += 1
        if tr is not None:
            tr.complete("replay", "launch", t_replay, time.perf_counter(),
                        args={"start_li": start_li, "chunk": chunk})
        if not with_head:
            return None
        logits = np.asarray(self._lm_head(x[:, -1:])[:, 0])
        self.stats.sync_pulls += 1
        self.stats.replay_pulls += 1
        return logits

    def decode(
        self,
        last_logits: np.ndarray,
        steps: int,
        *,
        greedy: bool = True,
        seed: int = 0,
        sampler: Optional[Any] = None,
    ) -> np.ndarray:
        """Generate ``steps`` tokens. Returns [B, steps].

        With ``spec_k > 1`` decode advances in speculative windows: each
        window emits up to ``spec_k`` tokens from ONE compiled program launch
        and one queue-draining pull (bit-identical to single-token decode —
        rejected positions are rolled back and replayed). This holds for
        SAMPLED decode too: pass ``sampler`` (a
        ``repro.serving.sampler.SamplerConfig``) or ``greedy=False`` (plain
        temperature-1.0 sampling seeded by ``seed``) and the fused path
        drafts on-device from the warped distribution with position-keyed
        draws, accepting via the stochastic rule — sampled fused decode
        always runs the scanned window family (size-1 windows when
        ``spec_k == 1``), so the spec-K and single-token streams are the
        same compiled program at different trip counts and match bitwise.
        """
        out = np.zeros((self.batch, steps), np.int32)
        logits = last_logits
        if sampler is None and not greedy:
            from repro.serving.sampler import SamplerConfig

            sampler = SamplerConfig(temperature=1.0, seed=seed)
        sampled = sampler is not None and sampler.temperature > 0.0
        sp = base_keys = sample_fn = sample_rng = None
        if sampled:
            sp = SampleParams(
                float(sampler.temperature), int(sampler.top_k),
                float(sampler.top_p),
            )
            base_keys = sampling_mod.row_keys(sampler.seed, self.batch)
            sample_fn = self._sample_fns.get(sp)
            if sample_fn is None:
                sample_fn = sampling_mod.build_sample_fn(sp)
                self._sample_fns[sp] = sample_fn
            sample_rng = np.random.default_rng(sampler.seed)
        spec = self._fused_decode and self.spec_k > 1
        t0 = time.perf_counter()
        i = 0
        while i < steps:
            if sampled:
                tok = np.asarray(sample_fn(
                    jnp.asarray(logits), base_keys,
                    jnp.int32(self.cur_len - 1),
                ))
                self.stats.sync_pulls += 1
            else:
                tok = np.argmax(logits, axis=-1).astype(np.int32)
            out[:, i] = tok
            t_win = time.perf_counter()
            k = min(self.spec_k, steps - i) if spec else 1
            if k > 1 or (sampled and self._fused_decode):
                extra, logits, committed = self._decode_window_fused(
                    tok, k, sample=sp, rng_keys=base_keys,
                    sample_rng=sample_rng,
                )
                if committed > 1:
                    out[:, i + 1 : i + committed] = extra.T
                advanced = committed
            else:
                if self._fused_decode:
                    logits = self._decode_step_fused(tok)
                elif self._hot_decode:
                    logits = self._decode_step_hot(tok)
                else:
                    x = self._embed(jnp.asarray(tok)[:, None])
                    x = self._run_layers(x, "decode", cur_len=self.cur_len)
                    logits = np.asarray(self._lm_head(x[:, -1:])[:, 0])
                    self.stats.sync_pulls += 1
                advanced = 1
            i += advanced
            self.cur_len += advanced
            self.stats.steps += advanced
            self.stats.tokens += self.batch * advanced
            self.metrics.histogram(
                "window_ms", "wall ms per decode step/window"
            ).observe((time.perf_counter() - t_win) * 1e3)
        self.stats.wall_s += time.perf_counter() - t0
        self.stats.compute_s = self.clock.compute_s
        self.stats.transfer_s = self.clock.transfer_s
        self.stats.stall_s = self.clock.stall_s
        self.stats.host_compute_s = self.clock.host_s
        self.last_logits = logits          # resume point for chained decodes
        return out

    def generate(self, prompt: np.ndarray, max_new: int, **kw) -> np.ndarray:
        logits = self.prefill(prompt)
        return self.decode(logits, max_new, **kw)
