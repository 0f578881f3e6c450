"""RotaryResidencyManager: per-MoE-layer slots + policy + LUT + accounting,
plus the startup feasibility check that reproduces the paper's Fig. 3 failure.

The manager owns host-side expert weights (the "warehouse" — full model in host
memory) and a ``SlotStore`` per MoE layer (the rotating accelerator-resident
subset). ``prepare_layer`` runs the policy's proactive transition and executes
the resulting uploads; ``resolve`` maps routed expert ids through the LUT and
classifies hits/misses.

Exactness invariant: residency state NEVER changes what an engine emits —
only where compute happens. Misses are classified (in-kernel on the hot
paths, via ``resolve`` on the walk) and corrected by the owning engine
(host GEMM + suffix replay / KV rollback), so outputs stay bit-identical to
full residency; under int8/int4 stores the correction runs against
dequant∘quant weights, keeping quantized serving exactness-clean within its
format.

Telemetry→transition map (the host half of each compiled step): the fused
engines hand one step's device-classified telemetry to
``rotate_from_telemetry`` (or a speculative window's to
``rotate_window_from_telemetry``, per-committed-step-equivalent with
uploads coalesced to the last write per slot): ``ids``/``weights`` fold into
the ``DemandPredictor`` EMA, ``miss`` + ``ids`` land in ``LayerStats`` via
``record_routing``, and ``demand_next`` (the pre-gating GEMM: on-device for
decode, the shared chunk-boundary program for chunked prefill) drives
``policy.prepare`` → ``RotaryRing`` transition → batched ``SlotStore``
uploads (one donated scatter per weight tensor per rotated layer) and
incremental device-LUT patches.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig, ResidencyConfig
from repro.core.policies import ResidencyPolicy, make_policy
from repro.core.slots import (
    SlotStore,
    quantized_expert_bytes,
    scatter_set,
    scatter_set_donated,
)
from repro.core.stats import EngineStats
from repro.core.transfer import CostModel, TransferClock
from repro.obs.metrics import BYTES_BUCKETS
from repro.obs.tracer import maybe_span, resolve_tracer


# Dirty-slot patches into the persistent stacked planes: one dispatch per
# weight tensor per rotated LAYER instead of a fresh jnp.stack over every rep
# in the segment. ``src`` ships whole (device gather beats a host slice) and
# the same program serves the [reps, E] LUT plane.
@functools.partial(jax.jit, donate_argnums=(0,))
def _plane_patch_rows_donated(plane, rep, idx, src):
    return plane.at[rep, idx].set(src[idx])


@jax.jit
def _plane_patch_rows(plane, rep, idx, src):
    return plane.at[rep, idx].set(src[idx])


# fused variant: ONE dispatch patches every weight-tensor plane of a layer's
# segment (pytree-mapped scatter) instead of one launch per tensor — the
# miss-relaunch path patches planes mid-step, so per-dispatch overhead is on
# the decode critical path, not just at rotation boundaries
@functools.partial(jax.jit, donate_argnums=(0,))
def _seg_patch_rows_donated(planes, rep, idx, src):
    return jax.tree_util.tree_map(
        lambda p, s: p.at[rep, idx].set(s[idx]), planes, src
    )


@jax.jit
def _seg_patch_rows(planes, rep, idx, src):
    return jax.tree_util.tree_map(
        lambda p, s: p.at[rep, idx].set(s[idx]), planes, src
    )


# write-through upload: ONE dispatch lands a rotation's host rows in the
# layer's store buffers AND the persistent stacked planes AND refreshes the
# stacked LUT row — the store scatter, the plane patch, and the LUT patch
# that used to be three separate launches. Only valid for unquantized stores
# (quantized planes hold the dequantized view, which the store must derive)
@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _write_through_donated(bufs, seg_slots, seg_lut, rep, idx, vals, e2s):
    bufs = jax.tree_util.tree_map(lambda b, v: b.at[idx].set(v), bufs, vals)
    seg_slots = jax.tree_util.tree_map(
        lambda p, v: p.at[rep, idx].set(v), seg_slots, vals
    )
    return bufs, seg_slots, seg_lut.at[rep].set(e2s)


@jax.jit
def _write_through(bufs, seg_slots, seg_lut, rep, idx, vals, e2s):
    bufs = jax.tree_util.tree_map(lambda b, v: b.at[idx].set(v), bufs, vals)
    seg_slots = jax.tree_util.tree_map(
        lambda p, v: p.at[rep, idx].set(v), seg_slots, vals
    )
    return bufs, seg_slots, seg_lut.at[rep].set(e2s)


# stacked-LUT row refresh: the per-layer LUT is a tiny [E] int32 vector, so a
# fixed-shape full-row set beats an index-specialized scatter (every distinct
# dirty count would compile its own program)
@functools.partial(jax.jit, donate_argnums=(0,))
def _lut_row_set_donated(plane, rep, src):
    return plane.at[rep].set(src)


@jax.jit
def _lut_row_set(plane, rep, src):
    return plane.at[rep].set(src)


def _bucket_rows(idx: np.ndarray, cap: int) -> np.ndarray:
    """Pad a row-index vector to the next power-of-two bucket (capped): row
    scatters/gathers shape-specialize on the index length, and duplicate
    indices write the same row twice (idempotent), so a handful of bucketed
    programs serve every dirty-set size instead of one compile per count."""
    n = int(idx.size)
    b = 1
    while b < n:
        b <<= 1
    b = min(b, cap) if n <= cap else n
    if n < b:
        idx = np.pad(idx, (0, b - n), mode="edge")
    return idx


class InitializationError(RuntimeError):
    """Startup failure (the paper's 'failed to initialize', Fig. 3 N36/4096)."""


@dataclass
class FeasibilityReport:
    ok: bool
    reason: str
    slot_bytes: int
    kv_bytes: int
    static_bytes: int            # non-MoE weights always resident
    activation_bytes: int
    total_bytes: int
    budget_bytes: Optional[int]
    min_slots: int


def _attention_static_bytes(cfg: ModelConfig, dtype_bytes: int = 2) -> int:
    """Weights that always stay on-device: everything except routed experts."""
    from repro.models.params import analytic_params

    total = analytic_params(cfg, active_only=False)
    if cfg.has_moe:
        m = cfg.moe
        mats = 3 if cfg.mlp == "swiglu" else 2
        routed = sum(
            m.num_experts * mats * cfg.d_model * m.expert_d_ff
            for k in cfg.layer_kinds if k == "attn_moe"
        )
        total -= routed
    return total * dtype_bytes


def check_feasibility(
    cfg: ModelConfig,
    rescfg: ResidencyConfig,
    *,
    batch: int,
    cache_len: int,
    dtype_bytes: int = 2,
) -> FeasibilityReport:
    """Two-sided startup check:

    (1) capacity floor — ``num_slots >= top_k + prefetch_margin`` so one step's
        routed experts plus in-flight prefetch fit (the N36-analog violates it);
    (2) memory ceiling — slots + pinned shared + KV + static weights +
        activation bound must fit ``hbm_budget_bytes``.
    """
    m = cfg.moe
    moe_layers = sum(1 for k in cfg.layer_kinds if k == "attn_moe")
    mats = 3 if cfg.mlp == "swiglu" else 2
    # exact packed bytes per expert (int4 includes its group scale/min planes)
    shapes = {"w_up": (cfg.d_model, m.expert_d_ff), "w_down": (m.expert_d_ff, cfg.d_model)}
    if mats == 3:
        shapes["w_gate"] = (cfg.d_model, m.expert_d_ff)
    expert_bytes = quantized_expert_bytes(
        shapes, rescfg.quantization, dtype_bytes, rescfg.quant_group_size
    )
    slots = rescfg.num_slots or m.num_experts
    min_slots = m.top_k + rescfg.prefetch_margin
    slot_bytes = moe_layers * (slots + 1) * expert_bytes

    kv_bytes = 0
    if cfg.uses_kv_cache:
        a = cfg.attention
        for k in cfg.layer_kinds:
            if k in ("attn_mlp", "attn_moe", "local_attn"):
                cap = min(a.window, cache_len) if (k == "local_attn" and a.window) else cache_len
                kv_bytes += 2 * batch * cap * a.num_kv_heads * a.head_dim * dtype_bytes
    static_bytes = _attention_static_bytes(cfg, dtype_bytes)
    act_bytes = 8 * batch * cfg.d_model * dtype_bytes * 16
    total = slot_bytes + kv_bytes + static_bytes + act_bytes

    if rescfg.mode != "full" and slots < min_slots:
        return FeasibilityReport(
            False,
            f"num_slots={slots} < top_k({m.top_k}) + prefetch_margin"
            f"({rescfg.prefetch_margin}) = {min_slots}: no startup margin",
            slot_bytes, kv_bytes, static_bytes, act_bytes, total,
            rescfg.hbm_budget_bytes, min_slots,
        )
    if rescfg.hbm_budget_bytes is not None and total > rescfg.hbm_budget_bytes:
        return FeasibilityReport(
            False,
            f"resident bytes {total/2**30:.2f} GiB exceed budget "
            f"{rescfg.hbm_budget_bytes/2**30:.2f} GiB",
            slot_bytes, kv_bytes, static_bytes, act_bytes, total,
            rescfg.hbm_budget_bytes, min_slots,
        )
    return FeasibilityReport(
        True, "ok", slot_bytes, kv_bytes, static_bytes, act_bytes, total,
        rescfg.hbm_budget_bytes, min_slots,
    )


class RotaryResidencyManager:
    """Owns residency state for every MoE layer of one model instance."""

    def __init__(
        self,
        cfg: ModelConfig,
        rescfg: ResidencyConfig,
        host_experts: List[Dict[str, np.ndarray]],   # per MoE layer: {w_*: [E, ...]}
        *,
        batch: int,
        cache_len: int,
        cost: Optional[CostModel] = None,
        stats: Optional[EngineStats] = None,
        seed: int = 0,
        tracer=None,
        metrics=None,
    ):
        report = check_feasibility(cfg, rescfg, batch=batch, cache_len=cache_len)
        if not report.ok:
            raise InitializationError(report.reason)
        self.cfg = cfg
        self.rescfg = rescfg
        self.report = report
        self.cost = cost or CostModel()
        self.stats = stats or EngineStats()
        # optional observability handles threaded by the owning engine; both
        # default to None and every emission site is guarded, so the
        # untraced hot path is untouched
        self.tracer = resolve_tracer(tracer)
        self.metrics = metrics
        self.host_experts = host_experts
        m = cfg.moe
        slots = rescfg.num_slots or m.num_experts
        if rescfg.mode == "full":
            slots = m.num_experts
        self.num_slots = slots
        # batched uploads may donate the replaced device buffers; engines whose
        # decode path never holds residency snapshots across a rotation (the
        # fused whole-stack step, the serving tick) flip this on
        self.donate_buffers = False
        dtype = jnp.dtype(cfg.dtype)
        self.stores: List[SlotStore] = []
        self.policies: List[ResidencyPolicy] = []
        for li, hw in enumerate(host_experts):
            shapes = {name: tuple(w.shape[1:]) for name, w in hw.items()}
            store = SlotStore(
                slots, shapes, dtype, rescfg.quantization,
                group_size=rescfg.quant_group_size,
            )
            policy = make_policy(rescfg.mode, m.num_experts, slots, rescfg, seed=seed + li)
            # full policy: preload everything (identity LUT) in one batch,
            # into the fresh buffers (donated: nothing else holds them yet)
            if rescfg.mode == "full":
                self.stats.bytes_uploaded += store.write_batch(
                    list(range(m.num_experts)), dict(hw), donate=True
                )
            self.stores.append(store)
            self.policies.append(policy)
        # persistent device-resident LUT per layer (patched incrementally on
        # rotation; never re-materialized per decode layer)
        self._lut_dev: List[Optional[jnp.ndarray]] = [None] * len(host_experts)
        # ONE generation counter keys every stacked device copy (slot planes
        # AND the stacked LUT plane): bumped whenever live residency content
        # changes — a live upload, a shadow flip. ``stacked_residency`` returns
        # its persistent planes untouched while generations match, else
        # scatters only the dirty slots tracked per layer below.
        self.generation = 0
        self._planes: Optional[Tuple[Any, ...]] = None
        self._planes_gen = -1
        self._stacked_dirty: List[set] = [set() for _ in host_experts]
        # MoE layer -> (segment index, rep) once planes exist: the upload
        # write-through path patches the layer's plane rows in the same fused
        # dispatch as the store scatter
        self._seg_of_layer: Dict[int, Tuple[int, int]] = {}
        # -- predictive prefetch (double-buffered generations) --------------
        # Enabled by the owning engine via ``enable_prefetch``. While a window
        # computes, ``begin_prefetch`` ships the SIMULATED next transition's
        # uploads into each store's shadow generation; the boundary's
        # authoritative transition then confirms (pointer flip), corrects
        # (mispredicted slots re-uploaded into the shadow BEFORE the flip), or
        # catches up (device-to-device copy for slots the shadow merely lags
        # on). ``_pending`` holds the speculative plan between the two.
        self._prefetch_enabled = False
        self._pending: Optional[List[List[Tuple[int, int, bool]]]] = None
        self._live_contents: Optional[List[Dict[int, int]]] = None
        self._shadow_contents: Optional[List[Dict[int, int]]] = None
        # adaptive speculation cadence: a stale forecast on near-uniform
        # routing mostly simulates EMPTY plans, so consecutive empties back
        # the re-simulation interval off exponentially (any landed plan
        # resets it) — the planner's host cost then tracks its hit rate
        self._sim_backoff = 1
        self._sim_skip = 0

    # ------------------------------------------------------------------
    def _transition(
        self,
        layer: int,
        demand: np.ndarray,
        steer: Optional[np.ndarray] = None,
    ) -> List[Tuple[int, int]]:
        """Run the policy's proactive transition (ring move + LUT updates) and
        account its rotation decision; returns the loads WITHOUT executing
        them — the window rotation path coalesces loads across steps before
        uploading. ``steer`` is the fresh pre-gating sample predictive
        steering retargets slots on (ignored at margin 0, the sync baseline)."""
        policy = self.policies[layer]
        loads = policy.prepare(demand, steer)
        ls = self.stats.layer(layer)
        decision = getattr(policy, "last_decision", None)
        if decision is not None:
            if decision.reverse_jump:
                ls.reverse_rotations += 1
            elif decision.delta:
                ls.forward_rotations += 1
        return loads

    def prepare_layer(
        self,
        layer: int,
        demand: np.ndarray,
        clock: Optional[TransferClock] = None,
        steer: Optional[np.ndarray] = None,
    ) -> int:
        """Run the proactive policy transition; execute uploads. Returns bytes."""
        loads = self._transition(layer, demand, steer)
        moved = self._execute_loads(layer, loads)
        ls = self.stats.layer(layer)
        ls.loads += len(loads)
        ls.bytes_loaded += moved
        if clock is not None:
            clock.prefetch(moved)
        return moved

    def _execute_loads(
        self, layer: int, loads: List[Tuple[int, int]], *, shadow: bool = False
    ) -> int:
        """Upload ``loads`` as ONE stacked scatter per weight tensor (not one
        dispatch per expert); old buffers are donated when the owning engine
        marked it safe. ``shadow`` lands the bytes in the store's shadow
        generation (speculative prefetch: the in-flight launch keeps reading
        untouched live buffers) instead of the live one.

        Traced as one ``upload`` span (``layer``, ``bytes``, ``n``,
        ``shadow``): the host's gather of the expert rows and its dispatch
        of the asynchronous copy and scatter, not the transfer itself, which
        runs on after the span closes."""
        if not loads:
            return 0
        hw = self.host_experts[layer]
        store = self.stores[layer]
        experts = np.asarray([e for e, _ in loads], np.int64)
        slots = [s for _, s in loads]
        track = "prefetch" if shadow else "rotation"
        with maybe_span(self.tracer, "upload", track) as span:
            if (
                not shadow
                and self._planes is not None
                and store.quantization is None
                and layer in self._seg_of_layer
            ):
                moved = self._write_through_loads(layer, slots, experts)
            else:
                before = store.dispatches
                moved = store.write_batch(
                    slots, {n: hw[n][experts] for n in hw},
                    donate=self.donate_buffers, shadow=shadow,
                )
                self.stats.upload_dispatches += store.dispatches - before
                self.stats.device_dispatches += store.dispatches - before
                self.stats.bytes_uploaded += moved
                if not shadow:
                    self._stacked_dirty[layer].update(int(s) for _, s in loads)
                    self.generation += 1
            if span is not None:
                span.args = {"layer": layer, "bytes": moved, "n": len(loads),
                             "shadow": shadow}
        if self._live_contents is not None:
            tracked = self._shadow_contents if shadow else self._live_contents
            for e, s in loads:
                tracked[layer][int(s)] = int(e)
        if self.metrics is not None:
            self.metrics.histogram(
                "upload_bytes", "bytes per slot-upload dispatch",
                buckets=BYTES_BUCKETS,
            ).observe(moved)
        return moved

    def _write_through_loads(
        self, layer: int, slots: List[int], experts: np.ndarray
    ) -> int:
        """Live upload fused with the plane patch: one compiled dispatch lands
        the host rows in the layer's store buffers AND its stacked slot-plane
        rows AND refreshes the stacked LUT row, replacing the store scatter +
        deferred ``stacked_residency`` patch pair. Unquantized stores only —
        a quantized plane holds the dequantized view, which only the store's
        two-phase path derives. Bit-exactness: the plane rows receive exactly
        the bytes the deferred d2d patch would have gathered from the store."""
        store = self.stores[layer]
        hw = self.host_experts[layer]
        lut = self.policies[layer].lut
        si, rep = self._seg_of_layer[layer]
        seg = self._planes[si]
        idx_np = np.asarray(slots, np.int32)
        vals = {n: np.asarray(hw[n][experts], store.dtype) for n in hw}
        moved = sum(int(v.nbytes) for v in vals.values())
        pad = _bucket_rows(idx_np, lut.num_slots)
        if pad.size > idx_np.size:
            extra = pad.size - idx_np.size
            vals = {
                n: np.concatenate([v, np.repeat(v[-1:], extra, axis=0)])
                for n, v in vals.items()
            }
        fn = _write_through_donated if self.donate_buffers else _write_through
        store.buffers, seg["slots"], seg["lut"] = fn(
            store.buffers, seg["slots"], seg["lut"],
            jnp.int32(rep), jnp.asarray(pad), vals, jnp.asarray(lut.e2s),
        )
        store.version += 1
        store.dispatches += 1
        store.bytes_uploaded += moved
        lut.take_dirty("stacked")        # the fused row set absorbed it
        self.stats.upload_dispatches += 1
        self.stats.device_dispatches += 1
        self.stats.bytes_uploaded += moved
        self.generation += 1
        # the planes are current for THIS layer; they lag only if another
        # layer still holds a dirty backlog — keep the generation key honest
        if not any(self._stacked_dirty) and not any(
            p.lut.dirty_count("stacked") for p in self.policies
        ):
            self._planes_gen = self.generation
        return moved

    def resolve(
        self, layer: int, ids: np.ndarray, clock: Optional[TransferClock] = None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Routed ids [T, k] -> (lut array [E], miss mask [T, k]).

        LRU-style policies may answer a miss with a blocking load (charged to the
        clock as a stall); others leave misses to host compute.
        """
        policy = self.policies[layer]
        policy.touch(np.unique(ids))
        lut = policy.lut
        miss = lut.e2s[ids] == lut.miss
        if miss.any():
            for e in np.unique(ids[miss]):
                load = policy.on_miss(int(e))
                if load is not None:
                    moved = self._execute_loads(layer, [load])
                    ls = self.stats.layer(layer)
                    ls.loads += 1
                    ls.bytes_loaded += moved
                    if clock is not None:
                        clock.blocking(moved)
            miss = lut.e2s[ids] == lut.miss
        ls = self.stats.layer(layer)
        ls.hits += int((~miss).sum())
        ls.misses += int(miss.sum())
        return lut.as_array(), miss

    # ------------------------------------------------------------------
    def device_lut(self, layer: int) -> jnp.ndarray:
        """The persistent device copy of ``layer``'s LUT.

        First call uploads the full [E] int32 table; later calls patch only the
        entries the policy mutated since (``SlotLUT.take_dirty``), so steady-
        state rotation costs a handful of scattered int32 updates instead of a
        fresh host->device array per MoE layer per decode step.
        """
        lut = self.policies[layer].lut
        cached = self._lut_dev[layer]
        if cached is None:
            lut.take_dirty()
            cached = jnp.asarray(lut.as_array())
        elif lut.dirty_count():
            old = cached
            if lut.dirty_count() > lut.num_experts // 2:
                # full re-upload beats a near-total scatter; the replaced
                # device array is dropped eagerly instead of waiting for GC
                lut.take_dirty()
                cached = jnp.asarray(lut.as_array())
                if self.donate_buffers:
                    old.delete()
            else:
                idx = lut.take_dirty()
                patch = scatter_set_donated if self.donate_buffers else scatter_set
                cached = patch(
                    old, jnp.asarray(idx, jnp.int32), jnp.asarray(lut.e2s[idx])
                )
                self.stats.lut_patch_dispatches += 1
                self.stats.device_dispatches += 1
        self._lut_dev[layer] = cached
        return cached

    def record_routing(self, layer: int, ids: np.ndarray, miss: np.ndarray) -> None:
        """Hit/miss accounting + policy usage feedback for routing that was
        classified ON DEVICE (hot path) — the bookkeeping half of ``resolve``
        without the host-side LUT lookup or reactive loads."""
        self.policies[layer].touch(np.unique(ids))
        ls = self.stats.layer(layer)
        ls.hits += int((~miss).sum())
        ls.misses += int(miss.sum())

    def ensure_resident(
        self, layer: int, experts: np.ndarray, avoid: np.ndarray
    ) -> Optional[List[Tuple[int, int]]]:
        """Make ``experts`` resident NOW (miss-relaunch correction): assign
        each missing one a slot whose current occupant is not in ``avoid``
        (the step's full routed set — evicting one of those would convert a
        hit into a fresh miss), upload as one batched scatter, and leave the
        incremental plane/LUT patching to pick the rows up off the shared
        generation counter. Returns the loads, or None when the residency
        cannot cover (more distinct routed experts than slots) — the caller
        falls back to the host-corrected suffix replay."""
        policy = self.policies[layer]
        lut = policy.lut
        need = [int(e) for e in np.unique(experts) if not lut.is_resident(int(e))]
        if not need:
            return []
        avoid_set = set(int(e) for e in avoid)
        free = list(lut.free_slots)
        evictable = [
            s for s in range(lut.num_slots)
            if lut.s2e[s] >= 0 and int(lut.s2e[s]) not in avoid_set
        ]
        ring = getattr(policy, "ring", None)
        if ring is not None:
            # evict the long-horizon-coldest occupants first: the correction
            # is reactive, so the displaced expert should be the one least
            # likely to be routed (and re-uploaded) next step
            evictable.sort(key=lambda s: (ring.ema[int(lut.s2e[s])], s))
        if len(free) + len(evictable) < len(need):
            return None
        loads: List[Tuple[int, int]] = []
        for e in need:
            slot = free.pop(0) if free else evictable.pop(0)
            lut.assign(e, slot)
            loads.append((e, slot))
        moved = self._execute_loads(layer, loads)
        ls = self.stats.layer(layer)
        ls.loads += len(loads)
        ls.bytes_loaded += moved
        return loads

    # -- predictive prefetch over double-buffered generations ------------
    def enable_prefetch(self, margin: Optional[int] = None) -> None:
        """Switch the manager to double-buffered prefetch mode: materialize a
        shadow generation per store, start tracking slot contents of both
        generations, and hand every policy its steering margin
        (``ResidencyConfig.prefetch_margin`` unless overridden). Must never be
        called on the synchronous baseline — the margin changes which experts
        transitions target (hotter, off-ring ones), which is exactly what
        shrinks the miss rate prefetch needs to pay for itself."""
        if self._prefetch_enabled:
            return
        if margin is None:
            margin = self.rescfg.prefetch_margin
        for p in self.policies:
            p.prefetch_margin = int(margin)
        self._live_contents = [
            {int(s): int(e) for s, e in enumerate(p.lut.s2e) if e >= 0}
            for p in self.policies
        ]
        for store in self.stores:
            store.ensure_shadow()
        self._shadow_contents = [dict(d) for d in self._live_contents]
        self._prefetch_enabled = True

    def begin_prefetch(self, predictor, clock: Optional[TransferClock] = None) -> int:
        """Ship the predicted next transition's uploads into the shadow
        generation — called right after a window launch is dispatched (and its
        telemetry pulls queued), so every bit of this host work and every
        shadow scatter overlaps the in-flight device compute. The plan comes
        from ``simulate_prepare`` on policy clones fed the predictor's current
        EMA (the pre-fold forecast of what the boundary will fold), so the
        authoritative ring/LUT state never advances speculatively. Returns
        bytes shipped; the boundary's ``_commit_layer`` scores the plan."""
        if not self._prefetch_enabled or self._pending is not None:
            return 0
        if self._sim_skip > 0:
            self._sim_skip -= 1
            return 0
        t0 = time.perf_counter()
        pending: List[List[Tuple[int, int, bool]]] = []
        launched = 0
        total = 0
        for l in range(len(self.policies)):
            plan = self.policies[l].simulate_prepare(
                predictor.forecast(l), predictor.steer_signal(l)
            )
            shadow = self._shadow_contents[l]
            entries: List[Tuple[int, int, bool]] = []
            ship: List[Tuple[int, int]] = []
            for e, s in plan:
                shipped = shadow.get(int(s)) != int(e)
                if shipped:
                    ship.append((int(e), int(s)))
                entries.append((int(e), int(s), shipped))
            moved = self._execute_loads(l, ship, shadow=True)
            launched += len(ship)
            total += moved
            pending.append(entries)
            if clock is not None:
                clock.prefetch(moved)
        self._pending = pending
        if launched:
            self._sim_backoff = 1
        else:
            self._sim_skip = self._sim_backoff
            self._sim_backoff = min(self._sim_backoff * 2, 16)
        self.stats.prefetch_launched += launched
        t1 = time.perf_counter()
        # legacy wall-clock accounting; when tracing is on, the SAME window
        # is also recorded as a ``prefetch_ship`` span so ``overlap_ms`` can
        # be derived from the trace and cross-checked against this counter
        self.stats.overlap_ms += (t1 - t0) * 1e3
        tr = self.tracer
        if tr is not None:
            tr.complete("prefetch_ship", "prefetch", t0, t1,
                        args={"bytes": total, "launched": launched})
        return total

    def _commit_layer(
        self,
        layer: int,
        loads: List[Tuple[int, int]],
        clock: Optional[TransferClock] = None,
    ) -> int:
        """Boundary reconciliation for one layer: score the speculative plan
        against the authoritative coalesced ``loads``, fix every slot where
        the shadow generation disagrees with the required post-transition
        contents, then flip. Order matters for exactness — corrections and
        catch-up copies land BEFORE the flip, so the generation the next
        launch gathers from is bit-identical to what the synchronous path
        would have produced with plain live uploads."""
        store = self.stores[layer]
        live = self._live_contents[layer]
        shadow = self._shadow_contents[layer]
        required = dict(live)
        for e, s in loads:
            required[int(s)] = int(e)
        plan = self._pending[layer] if self._pending is not None else []
        hits = 0
        wasted = 0
        useful = 0
        for e, s, shipped in plan:
            if required.get(s) == e:
                hits += 1
                if shipped:
                    useful += 1
            elif shipped:
                wasted += 1
        self.stats.prefetch_hits += hits
        self.stats.prefetch_wasted_bytes += wasted * store.bytes_per_expert
        tr = self.tracer
        if not loads:
            # nothing rotated: keep the live generation, let the shadow drift
            # (any speculative writes become next boundary's catch-up slots)
            if tr is not None and plan:
                tr.instant("prefetch_commit", "prefetch",
                           args={"layer": layer, "hits": hits,
                                 "wasted": wasted, "outcome": "drift"})
            return 0
        if useful == 0:
            # the shadow holds no byte this transition can reuse: the flip
            # protocol (corrections + d2d catch-up + pointer swap) would cost
            # strictly more dispatches than the synchronous path for zero
            # saved upload — take the plain live upload and let the shadow
            # keep drifting until a speculative plan actually lands
            moved = self._execute_loads(layer, loads)
            ls = self.stats.layer(layer)
            ls.loads += len(loads)
            ls.bytes_loaded += moved
            if clock is not None:
                clock.prefetch(moved)
            if tr is not None:
                tr.instant("prefetch_commit", "prefetch",
                           args={"layer": layer, "hits": hits,
                                 "wasted": wasted,
                                 "outcome": "live_fallback"})
            return moved
        # (1) mispredicted / unpredicted load slots: host-upload corrections
        corrections = [(e, s) for e, s in loads if shadow.get(int(s)) != int(e)]
        moved = self._execute_loads(layer, corrections, shadow=True)
        # (2) slots the shadow lags on (stale from drift or wasted writes):
        # device-to-device copy from live — no host-link traffic
        stale = sorted(
            s for s in set(live) | set(shadow) if shadow.get(s) != required.get(s)
        )
        if stale:
            n = store.sync_shadow_slots(stale, donate=self.donate_buffers)
            self.stats.device_dispatches += n
            for s in stale:
                shadow[s] = required[s]
        # (3) pointer flip: corrected shadow becomes live
        store.flip()
        self._live_contents[layer] = required
        self._shadow_contents[layer] = live
        self._stacked_dirty[layer].update(int(s) for _, s in loads)
        self.generation += 1
        if tr is not None:
            tr.instant("prefetch_commit", "prefetch",
                       args={"layer": layer, "hits": hits, "wasted": wasted,
                             "corrections": len(corrections),
                             "stale": len(stale), "outcome": "flip"})
        ls = self.stats.layer(layer)
        ls.loads += len(loads)
        ls.bytes_loaded += moved
        if clock is not None:
            clock.prefetch(moved)
        return moved

    def rotate_from_telemetry(
        self,
        predictor,
        ids: np.ndarray,
        weights: np.ndarray,
        miss: np.ndarray,
        demand_next: np.ndarray,
        clock: Optional[TransferClock] = None,
        record: bool = True,
    ) -> None:
        with maybe_span(self.tracer, "rotation", "rotation",
                        args={"kind": "step"}):
            return self._rotate_from_telemetry(
                predictor, ids, weights, miss, demand_next, clock, record)

    def _rotate_from_telemetry(
        self,
        predictor,                       # DemandPredictor
        ids: np.ndarray,                 # [L, T, k] routed expert ids
        weights: np.ndarray,             # [L, T, k] routing weights
        miss: np.ndarray,                # [L, T, k] device-classified misses
        demand_next: np.ndarray,         # [L, E]; row l = demand of layer (l+1)%L
        clock: Optional[TransferClock] = None,
        record: bool = True,
    ) -> None:
        """Between-step rotation + predictor feedback from ONE compiled step's
        telemetry — the host-side bookkeeping shared by the fused RotaryEngine
        step and the ServingEngine tick.

        ``demand_next`` is the on-device pre-gating signal (layer l's hidden
        through layer l+1's router, already softmaxed and token-averaged); the
        host only folds it into the EMA and runs the ring transition. With
        ``record`` the device-classified hit/miss masks are also accounted
        (the fused engine's replay path records its own authoritative masks
        and passes ``record=False``).
        """
        n = len(self.policies)
        for l in range(n):
            if record:
                self.record_routing(l, ids[l], miss[l])
            predictor.observe(l, ids[l], weights[l])
        for l in range(n):
            nxt = (l + 1) % n
            raw = demand_next[l]
            demand = predictor.update(nxt, raw)
            if self._pending is not None:
                loads = self._coalesce_loads(
                    nxt, self._transition(nxt, demand, steer=raw)
                )
                self._commit_layer(nxt, loads, clock)
            else:
                self.prepare_layer(nxt, demand, clock, steer=raw)
        self._pending = None

    def _coalesce_loads(
        self, layer: int, loads: List[Tuple[int, int]]
    ) -> List[Tuple[int, int]]:
        """Collapse a window's worth of pending loads to the last write per
        slot, dropping writes the LUT no longer references (an expert loaded
        then rotated away within the window never needs to touch the link)."""
        lut = self.policies[layer].lut
        final: Dict[int, int] = {}
        for e, s in loads:
            final[s] = e
        return [(e, s) for s, e in final.items() if lut.s2e[s] == e]

    def rotate_window_from_telemetry(
        self,
        predictor,
        ids: np.ndarray,
        weights: np.ndarray,
        miss: np.ndarray,
        demand_next: np.ndarray,
        clock: Optional[TransferClock] = None,
        record: bool = True,
        accepted: Optional[np.ndarray] = None,
    ) -> None:
        with maybe_span(self.tracer, "rotation", "rotation",
                        args={"kind": "window"}):
            return self._rotate_window_from_telemetry(
                predictor, ids, weights, miss, demand_next, clock, record,
                accepted)

    def _rotate_window_from_telemetry(
        self,
        predictor,                       # DemandPredictor
        ids: np.ndarray,                 # [K, L, T, k] routed ids per window step
        weights: np.ndarray,             # [K, L, T, k]
        miss: np.ndarray,                # [K, L, T, k]
        demand_next: np.ndarray,         # [K, L, E]; [s, l] = step s's demand
                                         # for layer (l+1)%L
        clock: Optional[TransferClock] = None,
        record: bool = True,
        accepted: Optional[np.ndarray] = None,
    ) -> None:
        """Window-boundary rotation from a speculative window's telemetry.

        The HOST-side transitions (EMA folds, ring moves, LUT updates) run
        once per committed step in step order — residency after the window is
        bit-identical to feeding the same steps through
        :meth:`rotate_from_telemetry` one at a time (the property the
        rotation-equivalence tests pin). What the window amortizes is the
        LINK: slot uploads coalesce to the last write per slot and ship as
        ONE batched scatter per weight tensor per layer per window, and the
        device LUT is patched once per layer instead of once per step.

        ``accepted`` (optional, [B] per-row committed counts) supports the
        serving engine's ragged acceptance: step ``s`` contributes a row's
        routing to the hit/miss accounting and the predictor EMA only while
        ``s < accepted[row]`` — a rejected position re-decodes next window
        and is recorded THEN, never twice, and routing computed from wrong
        drafted inputs never pollutes prediction. (The rotary engine commits
        batch-uniformly and pre-slices instead, leaving ``accepted=None``.)

        Sampled decode keeps the same commit discipline on its PRNG streams:
        a draw's key is ``fold_in(row_key, position)``, so a rejected
        position re-draws with the SAME key when it re-decodes — the stream
        commits like residency, per accepted position, and the emitted
        tokens depend only on (seed, position), never on window boundaries
        or batch composition.
        """
        n = len(self.policies)
        if accepted is not None:
            accepted = np.asarray(accepted)
            k_eff = int(accepted.max(initial=0))
            if k_eff == 0:
                return
            ids, weights, miss, demand_next = (
                a[:k_eff] for a in (ids, weights, miss, demand_next)
            )
        k_steps = ids.shape[0]

        def rows(s: int):
            return slice(None) if accepted is None else accepted > s

        pending: List[List[Tuple[int, int]]] = [[] for _ in range(n)]
        if record:
            for s in range(k_steps):
                for l in range(n):
                    self.record_routing(l, ids[s, l][rows(s)], miss[s, l][rows(s)])
        for l in range(n):
            nxt = (l + 1) % n
            if accepted is None:
                smoothed = predictor.fold_window(
                    nxt, ids[:, nxt], weights[:, nxt], demand_next[:, l]
                )
            else:
                smoothed = []
                for s in range(k_steps):
                    sel = rows(s)
                    predictor.observe(nxt, ids[s, nxt][sel], weights[s, nxt][sel])
                    smoothed.append(predictor.update(nxt, demand_next[s, l]))
            for s in range(k_steps):
                pending[nxt].extend(
                    self._transition(nxt, smoothed[s], steer=demand_next[s, l])
                )
        for l in range(n):
            loads = self._coalesce_loads(l, pending[l])
            if self._pending is not None:
                self._commit_layer(l, loads, clock)
                continue
            moved = self._execute_loads(l, loads)
            ls = self.stats.layer(l)
            ls.loads += len(loads)
            ls.bytes_loaded += moved
            if clock is not None:
                clock.prefetch(moved)
        self._pending = None

    # ------------------------------------------------------------------
    def layer_residency(self, layer: int) -> Dict[str, Any]:
        """{slots, lut} pytree for ``decode_model`` / ``_apply_block``."""
        return {
            "slots": self.stores[layer].as_pytree(),
            "lut": self.device_lut(layer),
        }

    def stacked_residency(self) -> Any:
        """Residency pytree stacked per segment (whole-model compiled path).

        PERSISTENT planes keyed on the manager's single ``generation`` counter
        (shared by the slot planes and the stacked LUT plane): the first call
        stacks full per-segment planes; every later call scatters only the
        slots that actually rotated since (``_stacked_dirty`` per layer, the
        LUT's "stacked" dirty backlog), donating the replaced plane when the
        owning engine marked donation safe. A boundary that rotated one layer
        costs a handful of row scatters instead of re-stacking whole segments.
        """
        if self._planes is not None and self._planes_gen == self.generation:
            return self._planes
        if self._planes is None:
            segs: List[Any] = []
            li = 0
            for unit, reps in self.cfg.segments:
                if not any(k == "attn_moe" for k in unit):
                    segs.append({})
                    continue
                per_rep = [self.layer_residency(li + r) for r in range(reps)]
                for r in range(reps):
                    # the full stack absorbs every backlog for these layers
                    self._stacked_dirty[li + r].clear()
                    self.policies[li + r].lut.take_dirty("stacked")
                    self._seg_of_layer[li + r] = (len(segs), r)
                li += reps
                segs.append({
                    "slots": {
                        n: jnp.stack([p["slots"][n] for p in per_rep])
                        for n in per_rep[0]["slots"]
                    },
                    "lut": jnp.stack([p["lut"] for p in per_rep]),
                })
            self._planes = tuple(segs)
            self._planes_gen = self.generation
            return self._planes
        patch = _seg_patch_rows_donated if self.donate_buffers else _seg_patch_rows
        lut_set = _lut_row_set_donated if self.donate_buffers else _lut_row_set
        li = 0
        for seg, (unit, reps) in zip(self._planes, self.cfg.segments):
            if not seg:
                continue
            for r in range(reps):
                l = li + r
                rep_i = jnp.int32(r)
                dirty = self._stacked_dirty[l]
                if dirty:
                    idx_np = _bucket_rows(
                        np.asarray(sorted(dirty), np.int32),
                        self.policies[l].lut.num_slots,
                    )
                    idx = jnp.asarray(idx_np)
                    dirty.clear()
                    src = self.stores[l].as_pytree()
                    seg["slots"] = patch(seg["slots"], rep_i, idx, src)
                    self.stats.device_dispatches += 1
                lut = self.policies[l].lut
                lidx = lut.take_dirty("stacked")
                if len(lidx):
                    seg["lut"] = lut_set(seg["lut"], rep_i, jnp.asarray(lut.e2s))
                    self.stats.lut_patch_dispatches += 1
                    self.stats.device_dispatches += 1
            li += reps
        self._planes_gen = self.generation
        return self._planes

    def host_expert_flops(self, tokens: int) -> float:
        m = self.cfg.moe
        mats = 3 if self.cfg.mlp == "swiglu" else 2
        return 2.0 * tokens * mats * self.cfg.d_model * m.expert_d_ff
