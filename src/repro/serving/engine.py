"""Request-level continuous batching over a paged KV pool.

The serving engine runs every decode tick as ONE compiled window launch over
whatever requests are live *right now*: rows join and leave the window
BETWEEN launches. A finishing request frees its KV pages immediately
(`repro.serving.kv_pool.KVPagePool`); the next queued request prefills into
the freed pages and joins the very next window — no group drain, no idle KV.
Admission is driven by page-pool pressure (worst-case page reservations at
admit; lazy physical allocation that therefore never fails mid-flight), not
batch geometry.

KV lives in SHARED paged planes (`tfm.paged_zero_state`): per layer, one
[reps, num_pages + 1, page_size, Hkv, dh] plane addressed through per-row
page tables (physical page 0 is pad/scratch). `attention_decode(page_table=
...)` gathers each row's logical view back to the contiguous layout before
scoring, so paged decode is BITWISE equal to a contiguous cache holding the
same logical KV — the exactness contract (every request's tokens identical to
a batch-1 run of that request alone) survives the refactor, with rotation /
prediction telemetry masked per committed row (``accepted=[B]``) exactly as
the speculative window path does.

Compile-cache story: programs are keyed on WINDOW GEOMETRY, not live-row
count — the live rows pack into a power-of-two rows bucket (pad rows carry
all-zero page tables, write into the scratch page, and are masked everywhere
with ``accepted = 0``), so at most log2(num_slots)+1 row shapes exist per
window length K, however requests churn. Speculation, bucketed admission
prefill, per-row accept/rollback, and deadline handling all carry over; a
size-1 window IS the plain tick (same program family, same telemetry path).

Recurrent archs (and ``paged=False``) keep the previous group-tick path: a
fixed contiguous decode batch stepped via ``build_fused_decode_step``, rows
claimed/freed by the scheduler — recurrent state is per-row by construction
and cannot live in a shared page plane.

Device-residency hot-path details shared with the rotary engine: the
compiled window IS the engine's fused whole-stack program
(``build_fused_window_step``) — KV pool donated, demand prediction on-device
— the stacked residency pytree is CACHED per segment, per-layer LUTs are
persistent device arrays patched in place, routing / demand telemetry rides
async D2H copies issued before the draft pull, and the between-window
rotation is the manager's ``rotate_window_from_telemetry`` with per-row
accepted counts masking pad rows and rejected suffixes.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import ModelConfig, ResidencyConfig
from repro.core.engine import (
    build_fused_decode_step,
    build_fused_prefill_step,
    build_window_fns,
    concat_route_telemetry,
    moe_segments,
    prefill_chunk_plan,
    split_expert_store,
)
from repro.core.predictor import DemandPredictor
from repro.core.residency import RotaryResidencyManager
from repro.core.stats import EngineStats
from repro.models import transformer as tfm
from repro.models import sampling as sampling_mod
from repro.models.sampling import SampleParams
from repro.models.transformer import Runtime
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracer import resolve_tracer
from repro.serving.kv_pool import KVPagePool
from repro.serving.sampler import Sampler, SamplerConfig, stochastic_accept
from repro.serving.scheduler import Request, Scheduler

_KV_ONLY_KINDS = ("attn_mlp", "attn_moe", "local_attn")


class ServingEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        *,
        rt: Optional[Runtime] = None,
        num_slots: int = 4,
        residency: Optional[ResidencyConfig] = None,
        sampler: Optional[SamplerConfig] = None,
        eos: Optional[int] = None,
        spec_cap: int = 4,
        bucketed_prefill: bool = True,
        paged: Optional[bool] = None,
        kv_page_size: int = 16,
        kv_pages: Optional[int] = None,
        prefetch: bool = False,
        trace=None,
    ):
        """``spec_cap`` bounds per-row speculative decode: when sampling is
        greedy and the stack is KV-cache-only, windows self-draft up to the
        SCHEDULER's learned per-row speculative lengths (``spec_cap=1``
        disables speculation).

        ``bucketed_prefill`` routes each tick's admitted requests through ONE
        shared compiled prefill program at the scheduler-chosen power-of-two
        bucket (rows padded to the power-of-two cover of the group size,
        per-row ``last_index`` for the ragged lengths) instead of one batch-1
        program launch per request. Per-row outputs are identical to the
        batch-1 path. Recurrent archs need exact-length prefills and keep the
        batch-1 path regardless.

        ``paged`` selects the continuous-batching paged KV pool (module
        docstring); default: on for KV-cache-only stacks, off (group-tick
        path) for recurrent archs. ``kv_page_size`` is the positions-per-page
        granularity (clamped to the largest divisor of the per-row cache
        capacity); ``kv_pages`` overrides the pool size in pages (default
        ``num_slots`` full rows — the same KV memory the contiguous batch
        held, now fluid across requests).

        ``prefetch`` enables asynchronous predictive expert prefetch on the
        paged tick: while a window launch is in flight, the predicted next
        boundary's uploads land in the slot stores' SHADOW generation and the
        tick boundary becomes confirm/correct/flip
        (``RotaryResidencyManager.begin_prefetch`` / ``_commit_layer``).
        Unlike the rotary engine, serving enables it with steering margin 0:
        the paged tick has no replay path (a missed position commits with
        the expert dropped), so transitions must stay byte-identical to the
        synchronous baseline for outputs to stay byte-identical — only the
        overlap is bought. Requires the paged pool and a rotating residency
        manager.

        ``trace`` (a ``repro.obs.Tracer``) records launch/pull/rotation/
        prefetch spans plus one lane per request (queued → prefill → decode
        → finish) and the KV pool's page events; ``None``/disabled leaves
        every hot path untouched (all emission sites are guarded)."""
        self.cfg = cfg
        self.params = params
        self.rt = rt or Runtime(cache_len=1024)
        self.batch = num_slots
        self.eos = eos
        self.sampler = Sampler(sampler or SamplerConfig())
        self.stats = EngineStats()
        self._tr = resolve_tracer(trace)
        self.tracer = self._tr
        self.metrics = MetricsRegistry()
        kv_only = all(k in _KV_ONLY_KINDS for k in cfg.layer_kinds)
        if paged is None:
            paged = kv_only
        if paged and not kv_only:
            raise ValueError(
                "paged KV pool requires a KV-cache-only stack; recurrent "
                f"archs keep the group-tick path ({cfg.layer_kinds})"
            )
        self._paged = paged
        # sampled (temperature > 0) serving draws on-device with per-request
        # position-keyed PRNG streams (repro.models.sampling) on the paged
        # path; the group-tick path keeps the host Sampler
        self._sampled = self.sampler.cfg.temperature > 0.0
        self._sample_params = None
        self._sample_fn = None
        self._accept_rng = None
        self._req_keys: Dict[int, np.ndarray] = {}   # uid -> [2] uint32 base key
        if self._sampled:
            c = self.sampler.cfg
            self._sample_params = SampleParams(
                float(c.temperature), int(c.top_k), float(c.top_p)
            )
            self._sample_fn = sampling_mod.build_sample_fn(self._sample_params)
            self._accept_rng = np.random.default_rng(c.seed)
        # speculative windows need KV-only state (rollback restores cache
        # slots; a recurrent update is destructive). Sampled speculation runs
        # the stochastic accept rule over the window's sample_probs telemetry
        # — paged path only (the group tick draws through the host Sampler)
        self._spec_ok = (
            spec_cap > 1 and kv_only and (not self._sampled or paged)
        )
        from repro.models import attention as attn_mod

        cap = attn_mod._cache_capacity(cfg.attention, self.rt.cache_len)
        self._spec_cap_eff = 1
        if self._spec_ok:
            self._spec_cap_eff = max(1, min(spec_cap, cap))
            self._spec_ok = self._spec_cap_eff > 1
        self.scheduler = Scheduler(
            num_slots, spec_cap=self._spec_cap_eff,
            max_prompt_len=self.rt.cache_len,
        )

        self.lengths = np.zeros((self.batch,), np.int32)
        self.next_token = np.zeros((self.batch,), np.int32)
        self.active = np.zeros((self.batch,), bool)

        # --- KV: paged pool (continuous batching) or contiguous batch ----
        self.pool: Optional[KVPagePool] = None
        self.state = None                    # contiguous [B, cap, ...] caches
        self.pool_state = None               # shared paged planes
        if self._paged:
            page_size = max(1, min(kv_page_size, cap))
            while cap % page_size:
                page_size -= 1               # largest divisor <= kv_page_size
            row_pages = cap // page_size
            pages = kv_pages if kv_pages is not None else num_slots * row_pages
            if pages < row_pages:
                raise ValueError(
                    f"kv_pages={pages} cannot hold one full row "
                    f"({row_pages} pages of {page_size})"
                )
            self.pool = KVPagePool(pages, page_size, row_pages,
                                   tracer=self._tr)
            # physical plane index 0 is the scratch page pad rows write into
            self.pool_state = tfm.paged_zero_state(cfg, pages + 1, page_size)
        else:
            self.state = tfm.zero_state(cfg, self.batch, self.rt.cache_len)

        # --- residency (MoE archs only) --------------------------------
        self.res_mgr: Optional[RotaryResidencyManager] = None
        self.predictor: Optional[DemandPredictor] = None
        if residency is not None and residency.mode != "full" and cfg.has_moe:
            if not kv_only:
                raise ValueError(
                    "a rotating residency needs a KV-cache-only stack: exact "
                    "admission prefill reruns missed chunks over the same "
                    f"cache positions ({cfg.layer_kinds})"
                )
            # the expert warehouse stays in host memory; compiled programs
            # (admission prefill included) read experts through the slots
            self.params, host_experts, routers = split_expert_store(cfg, params)
            # feasibility prices KV bytes: the pool holds pages-worth of KV,
            # not num_slots full rows, so report the pool-equivalent batch
            batch_eff = self.batch
            if self.pool is not None:
                batch_eff = max(
                    1, -(-self.pool.num_pages * self.pool.page_size // cap)
                )
            self.res_mgr = RotaryResidencyManager(
                cfg, residency, host_experts,
                batch=batch_eff, cache_len=self.rt.cache_len, stats=self.stats,
                tracer=self._tr, metrics=self.metrics,
            )
            self.predictor = DemandPredictor(routers, ema=residency.predictor_ema)
            for li in range(len(host_experts)):
                self.res_mgr.prepare_layer(li, self.predictor.smoothed[li])
            # admission chunk: the largest power of two whose routed set
            # (chunk * top_k experts at most) fits the smallest slot count
            slots = min(p.lut.num_slots for p in self.res_mgr.policies)
            fit = slots // cfg.moe.top_k
            if fit < 1:
                raise ValueError(
                    f"num_slots {slots} < top_k {cfg.moe.top_k}: one token's "
                    "routed experts cannot all be resident, so admission "
                    "prefill cannot be exact"
                )
            self._resident_chunk = 1 << (fit.bit_length() - 1)

        # --- compiled steps ---------------------------------------------
        # ticks share the rotary engine's fused whole-stack programs: KV state
        # donated (no per-tick cache copy), per-layer demand GEMM in-graph.
        # Paged mode runs EVERY tick through the window family (a plain tick
        # is a size-1 window), so the single-token step is only built for the
        # group-tick path.
        self._routers_next = None
        if self.res_mgr is not None:
            self.res_mgr.donate_buffers = True       # no snapshots span a tick
            self._routers_next = jnp.asarray(self.predictor.next_layer_routers())
        self.prefetch = bool(prefetch)
        if self.prefetch:
            if self.res_mgr is None:
                raise ValueError(
                    "prefetch=True needs a rotating residency manager: pass a "
                    "non-full ResidencyConfig on an MoE architecture (full "
                    "residency never rotates, so there is nothing to prefetch)"
                )
            if not self._paged:
                raise ValueError(
                    "prefetch=True rides the paged continuous-batching tick; "
                    "the group-tick path rotates synchronously"
                )
            if any(
                getattr(p, "needs_sync_resolve", False)
                for p in self.res_mgr.policies
            ):
                raise ValueError(
                    "prefetch=True is incompatible with reactive (LRU-style) "
                    "policies: their mid-step blocking loads leave no "
                    "boundary to flip at"
                )
            # margin 0: see the docstring — serving has no replay path, so
            # the transition SEQUENCE must match the synchronous baseline
            self.res_mgr.enable_prefetch(margin=0)
        self._decode = None
        if not self._paged:
            self._decode = build_fused_decode_step(
                cfg, self.rt, with_demand=self.res_mgr is not None,
                donate_state=True,
                keep_replay_anchor=False,  # no replay path: drop route_x outputs
            )
        self._moe_segs = moe_segments(cfg)
        self._prefill_cache: Dict[int, Any] = {}
        self._bucket_prefill_cache: Dict[int, Any] = {}
        self._resident_prefill_cache: Dict[Tuple[int, bool], Any] = {}
        self._window_cache: Dict[int, Any] = {}
        self._paged_splice_cache: Dict[int, Any] = {}
        self._has_recurrence = any(
            k in ("mlstm", "slstm", "rglru") for k in cfg.layer_kinds
        )
        self._bucketed_prefill = bucketed_prefill and not self._has_recurrence

    def _window_fns(self, k: int):
        """Compiled (window step, KV snapshot, KV rollback) for window size
        ``k`` — the rotary engine's speculative triple, minus the replay path
        (so the window drops the ``route_x`` anchors). Sampled engines bake
        their warp params into the window (drafting becomes an on-device
        position-keyed draw). Paged mode keys its whole compile cache here:
        (K, rows bucket) geometry, never live-row count."""
        fns = self._window_cache.get(k)
        if fns is None:
            fns = build_window_fns(
                self.cfg, self.rt, k,
                with_demand=self.res_mgr is not None,
                keep_replay_anchor=False,
                sample=self._sample_params,
            )
            self._window_cache[k] = fns
        return fns

    def _request_key(self, req: Request) -> np.ndarray:
        """[2] uint32 PRNG base key for one request — a pure function of the
        request's seed (uid/slot/batch-independent), so its sampled stream is
        identical alone, mid-CB-window, or across prefetch relaunches."""
        key = self._req_keys.get(req.uid)
        if key is None:
            seed = req.seed if req.seed is not None else self.sampler.cfg.seed
            key = np.asarray(sampling_mod.request_key(int(seed)))
            self._req_keys[req.uid] = key
        return key

    # ------------------------------------------------------------------
    def _resident_step(self, c: int, with_head: bool) -> Callable:
        """Compiled ``c``-token prefill chunk through the residency slots
        (state NOT donated: a chunk that misses runs again from it)."""
        key = (c, with_head)
        fn = self._resident_prefill_cache.get(key)
        if fn is None:
            fn = build_fused_prefill_step(
                self.cfg, self.rt, with_demand=False, donate_state=False,
                with_head=with_head,
            )
            self._resident_prefill_cache[key] = fn
        return fn

    def _prefill_resident(self, prompt: np.ndarray) -> Tuple[np.ndarray, Any]:
        """Exact batch-1 admission prefill with the expert store in host
        memory: the prompt ingests in chunks of at most ``_resident_chunk``
        tokens, small enough that a chunk's routed set fits every layer's
        slots. A launch that reports a miss uploads its routed experts
        (``ensure_resident``) and runs the chunk again from the same state,
        until a launch is miss-free. Layers before the first missed one saw
        the same inputs, so each relaunch clears at least that layer: a chunk
        takes at most one launch per MoE layer plus one, and its logits and
        KV are what the full expert store computes. Each MoE layer's routing
        is recorded once per chunk, from the first launch whose input to it
        was exact. Returns (logits [1, V], row state)."""
        mgr = self.res_mgr
        n_moe = len(mgr.policies)
        plan = prefill_chunk_plan(len(prompt), self._resident_chunk)
        cold = any(
            (c, i == len(plan) - 1) not in self._resident_prefill_cache
            for i, c in enumerate(plan)
        )
        t0 = time.perf_counter()
        state = tfm.zero_state(self.cfg, 1, self.rt.cache_len)
        pos = 0
        for i, c in enumerate(plan):
            step = self._resident_step(c, i == len(plan) - 1)
            tokens = jnp.asarray(prompt[None, pos : pos + c])
            done = 0                  # MoE layers whose routing is recorded
            while True:
                logits, new_state, aux = step(
                    self.params, None, tokens, state, jnp.int32(pos),
                    mgr.stacked_residency(),
                )
                miss = concat_route_telemetry(aux, "miss", self._moe_segs)
                ids = concat_route_telemetry(aux, "ids", self._moe_segs)
                missed = np.flatnonzero(miss.reshape(n_moe, -1).any(axis=1))
                first = int(missed[0]) if missed.size else n_moe
                for li in range(done, min(first + 1, n_moe)):
                    mgr.record_routing(li, ids[li], miss[li])
                if not missed.size:
                    break
                for li in missed:
                    routed = np.unique(ids[li])
                    if mgr.ensure_resident(int(li), routed, routed) is None:
                        raise RuntimeError(
                            f"MoE layer {li}: {routed.size} routed experts "
                            f"exceed its slots; admission prefill cannot "
                            f"be made exact"
                        )
                done = first + 1
                self.stats.relaunched_steps += 1
            state = new_state
            pos += c
            self.stats.prefill_chunks += 1
        logits = np.asarray(logits)
        dt = time.perf_counter() - t0
        if not cold and dt > 0:
            self.scheduler.observe_prefill_rate(len(prompt) / dt)
        return logits, state

    def _prefill_one(self, prompt: np.ndarray) -> Any:
        """Batch-1 prefill at a power-of-two length bucket (right-padded;
        decode masks cache positions >= true length so pads never score).
        Recurrent archs use exact lengths — pads would pollute the state."""
        s = len(prompt)
        bucket = s if self._has_recurrence else Scheduler.prefill_bucket(
            [s], self.rt.cache_len
        )
        cold = bucket not in self._prefill_cache
        if cold:
            def fn(params, tokens, last):
                return tfm.prefill_model(
                    self.cfg, params, tokens, self.rt, last_index=last
                )

            self._prefill_cache[bucket] = jax.jit(fn)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :s] = prompt
        t0 = time.perf_counter()
        logits, state = self._prefill_cache[bucket](
            self.params, jnp.asarray(padded), jnp.asarray([s - 1], jnp.int32)
        )
        logits.block_until_ready()
        dt = time.perf_counter() - t0
        if not cold and dt > 0:
            # steady-state sample only — a cold bucket's wall time is
            # dominated by trace/compile and would poison the admission EMA
            self.scheduler.observe_prefill_rate(s / dt)
        return logits, state, s

    def _prefill_bucketed(self, admitted: List[Request]) -> List[Any]:
        """Prefill one admission group through the SHARED compiled bucketed
        program: the scheduler picks the power-of-two bucket covering every
        admitted prompt, the rows pad to the power-of-two cover of the group
        size (compile cache keyed on (bucket, rows) — at most log2(batch)
        row shapes per bucket, and a single admission doesn't pay the whole
        batch's worth of pad-row prefill work or depress the admission-rate
        EMA), and ONE program launch scans every row through exactly the
        per-row computation ``_prefill_one`` runs — per-row outputs match
        the batch-1 splice-in path. Rows splice into the live KV (contiguous
        row or allocated pages) with the ragged machinery (per-row
        ``last_index`` / ``lengths``).

        Returns [(request, logits [1, V], row_state)] per admitted request.
        """
        lens = [len(r.prompt) for r in admitted]
        bucket = Scheduler.prefill_bucket(lens, self.rt.cache_len)
        rows = min(self.batch, 1 << (len(admitted) - 1).bit_length())
        key = (bucket, rows)
        cold = key not in self._bucket_prefill_cache
        if cold:
            def fn(params, tokens, last):          # [rows, bucket], [rows]
                def row(_, xs):
                    tok, li = xs
                    logits, state = tfm.prefill_model(
                        self.cfg, params, tok[None], self.rt,
                        last_index=li[None],
                    )
                    return None, (logits[0], state)

                _, (logits, states) = jax.lax.scan(row, None, (tokens, last))
                return logits, states

            self._bucket_prefill_cache[key] = jax.jit(fn)
        padded = np.zeros((rows, bucket), np.int32)
        last = np.zeros((rows,), np.int32)
        for i, req in enumerate(admitted):
            padded[i, : len(req.prompt)] = req.prompt
            last[i] = len(req.prompt) - 1
        t0 = time.perf_counter()
        logits, states = self._bucket_prefill_cache[key](
            self.params, jnp.asarray(padded), jnp.asarray(last)
        )
        logits.block_until_ready()
        dt = time.perf_counter() - t0
        if not cold and dt > 0:
            # steady-state sample only — a cold bucket's wall time is
            # dominated by trace/compile and would poison the admission EMA
            self.scheduler.observe_prefill_rate(sum(lens) / dt)
        logits_np = np.asarray(logits)
        out = []
        for i, req in enumerate(admitted):
            row_state = jax.tree.map(lambda a, i=i: a[i], states)
            out.append((req, logits_np[i : i + 1], row_state))
        return out

    def _prefill_admitted(self, admitted: List[Request]) -> List[Any]:
        """Admission prefill: the exact slot-chunked path under a rotating
        residency, else the shared bucketed program by default, batch-1
        programs for recurrent archs / ``bucketed_prefill=False``."""
        if not admitted:
            return []
        if self.res_mgr is not None:
            return [(req, *self._prefill_resident(req.prompt))
                    for req in admitted]
        if self._bucketed_prefill:
            return self._prefill_bucketed(admitted)
        out = []
        for req in admitted:
            logits, row_state, _ = self._prefill_one(req.prompt)
            out.append((req, logits, row_state))
        return out

    def _splice_row(self, slot: int, row_state: Any) -> None:
        """Insert a batch-1 prefill state into contiguous batch row ``slot``."""
        def splice(dst, src):
            return dst.at[:, slot].set(src[:, 0])

        self.state = jax.tree.map(splice, self.state, row_state)

    def _paged_splice_fn(self, n: int):
        """Compiled ``n``-page join splice (cache keyed on page count —
        request lengths bucket to at most row_pages shapes)."""
        fn = self._paged_splice_cache.get(n)
        if fn is None:
            ps = self.pool.page_size

            def splice(pool_state, row_state, pg):
                def one(dst, src):
                    reps = src.shape[0]
                    blk = src[:, 0, : n * ps].reshape(
                        (reps, n, ps) + src.shape[3:]
                    )
                    return dst.at[:, pg].set(blk)

                return jax.tree.map(one, pool_state, row_state)

            fn = jax.jit(splice, donate_argnums=(0,))
            self._paged_splice_cache[n] = fn
        return fn

    def _splice_row_paged(self, uid: int, row_state: Any) -> None:
        """Insert a batch-1 prefill state's KV prefix into the pages request
        ``uid`` owns: ONE donated scatter over every pool plane per join."""
        pages = self.pool.table(uid)
        self.pool_state = self._paged_splice_fn(len(pages))(
            self.pool_state, row_state, jnp.asarray(pages, jnp.int32)
        )
        self.stats.device_dispatches += 1

    def _account_pages(self, grew: int) -> None:
        if grew:
            self.stats.kv_pages_allocated += grew
            self.stats.kv_pages_hwm = max(
                self.stats.kv_pages_hwm, self.pool.pages_in_use
            )

    def _release_request(self, req: Request) -> None:
        """A finished row leaves the window: its pages return to the pool NOW
        and the next queued request prefills into them at the next tick —
        the continuous-batching lever the group tick lacked."""
        tr = self._tr
        if tr is not None:
            # lane phase 3: first token -> finished (the decode stretch)
            t1 = req.finished_at or time.perf_counter()
            if req.first_token_at:
                tr.complete("decode", "request", req.first_token_at, t1,
                            lane=req.uid, args={"tokens": len(req.output)})
            tr.instant("finish", "request", lane=req.uid,
                       args={"tokens": len(req.output)})
        self._req_keys.pop(req.uid, None)
        if self.pool is not None:
            self.stats.kv_pages_released += self.pool.release(req.uid)

    # ------------------------------------------------------------------
    def warmup(self, max_prompt_len: int = 16) -> int:
        """Pre-compile the serving program family for a workload envelope
        (prompts up to ``max_prompt_len``): admission-prefill buckets x
        power-of-two group sizes, window K x rows buckets (paged) or the
        fixed-batch step/window family (group tick), and the paged splice
        programs for every reachable page count. Call BEFORE submitting
        traffic — first-request latency then measures serving, not tracing.

        Warmup launches write only throwaway positions (the paged programs
        write the scratch page; the group-tick programs touch row positions a
        request's splice fully overwrites) and touch no host bookkeeping or
        stats. Returns the number of programs compiled."""
        compiled = 0
        mp = max(1, min(max_prompt_len, self.rt.cache_len))
        # admission prefill: under a rotating residency every (chunk, head)
        # shape the envelope's chunk plans reach; else every power-of-two
        # bucket it reaches, at every power-of-two admission group size
        # (recurrent archs prefill at exact lengths — nothing reusable to
        # pre-compile)
        if self.res_mgr is not None:
            shapes = {
                (c, i == len(plan) - 1)
                for l in range(1, mp + 1)
                for plan in [prefill_chunk_plan(l, self._resident_chunk)]
                for i, c in enumerate(plan)
            }
            residency = self.res_mgr.stacked_residency()
            for c, head in sorted(shapes):
                if (c, head) not in self._resident_prefill_cache:
                    out = self._resident_step(c, head)(
                        self.params, None, jnp.zeros((1, c), jnp.int32),
                        tfm.zero_state(self.cfg, 1, self.rt.cache_len),
                        jnp.int32(0), residency,
                    )
                    jax.block_until_ready(out[1])
                    compiled += 1
        elif not self._has_recurrence:
            buckets = sorted({
                Scheduler.prefill_bucket([l], self.rt.cache_len)
                for l in range(1, mp + 1)
            })
            if self._bucketed_prefill:
                g = 1
                while g <= self.batch:
                    for b in buckets:
                        if (b, g) not in self._bucket_prefill_cache:
                            self._prefill_bucketed([
                                Request(-1 - i, np.zeros((b,), np.int32), 0)
                                for i in range(g)
                            ])
                            compiled += 1
                    g *= 2
            else:
                for b in buckets:
                    if b not in self._prefill_cache:
                        self._prefill_one(np.zeros((b,), np.int32))
                        compiled += 1
        ks = range(1, self._spec_cap_eff + 1) if self._spec_ok else (1,)
        residency = None
        if self.res_mgr is not None:
            residency = self.res_mgr.stacked_residency()
        if self._paged:
            for k in ks:
                step_fn, snap_fn, roll_fn = self._window_fns(k)
                rows = 1
                while rows <= self.batch:
                    pt = jnp.zeros((rows, self.pool.row_pages), jnp.int32)
                    tok = jnp.zeros((rows,), jnp.int32)
                    lens = jnp.zeros((rows,), jnp.int32)
                    keep = jnp.zeros((rows,), jnp.int32)
                    saved = None
                    if self.res_mgr is not None:
                        saved = snap_fn(self.pool_state, lens, pt)
                        compiled += 1
                    out = step_fn(
                        self.params, self._routers_next, tok,
                        self.pool_state, lens, residency, pt,
                    )
                    self.pool_state = out[2]
                    compiled += 1
                    if saved is not None:
                        self.pool_state = roll_fn(
                            self.pool_state, saved, lens, keep, pt
                        )
                        compiled += 1
                    rows *= 2
            for n in sorted({self.pool.pages_for(l) for l in range(1, mp + 1)}):
                if n not in self._paged_splice_cache:
                    fn = self._paged_splice_fn(n)
                    self.pool_state = fn(
                        self.pool_state,
                        tfm.zero_state(self.cfg, 1, self.rt.cache_len),
                        jnp.zeros((n,), jnp.int32),
                    )
                    compiled += 1
            jax.block_until_ready(self.pool_state)
            return compiled
        tok = jnp.zeros((self.batch,), jnp.int32)
        lens = jnp.zeros((self.batch,), jnp.int32)
        keep = jnp.zeros((self.batch,), jnp.int32)
        out = self._decode(
            self.params, self._routers_next, tok, self.state, lens, residency
        )
        self.state = out[1]
        compiled += 1
        for k in ks:
            if k == 1:
                continue
            step_fn, snap_fn, roll_fn = self._window_fns(k)
            saved = None
            if self.res_mgr is not None:
                saved = snap_fn(self.state, lens)
                compiled += 1
            out = step_fn(
                self.params, self._routers_next, tok, self.state, lens,
                residency,
            )
            self.state = out[2]
            compiled += 1
            if saved is not None:
                self.state = roll_fn(self.state, saved, lens, keep)
                compiled += 1
        jax.block_until_ready(self.state)
        return compiled

    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int,
               deadline_s: Optional[float] = None,
               seed: Optional[int] = None) -> Request:
        """``seed`` fixes this request's sampled PRNG stream (defaults to the
        engine sampler's seed); greedy engines ignore it."""
        prompt = np.asarray(prompt, np.int32)
        if self.pool is not None and len(prompt) > self.rt.cache_len:
            # up-front pool-capacity validation: this request could NEVER be
            # admitted, so fail loudly instead of queue-rejecting downstream
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the per-request KV "
                f"capacity {self.rt.cache_len} "
                f"({self.pool.row_pages} pages x {self.pool.page_size} "
                f"positions at full residency)"
            )
        return self.scheduler.submit(
            prompt, max_new, time.perf_counter(), deadline_s, seed=seed
        )

    def run(self, max_ticks: int = 10_000) -> List[Request]:
        """Drive until all submitted work completes. Returns completed requests."""
        ticks = 0
        t0 = time.perf_counter()
        while not self.scheduler.idle and ticks < max_ticks:
            self.tick()
            ticks += 1
        self.stats.wall_s += time.perf_counter() - t0
        if self.stats.wall_s > 0 and self.stats.steps:
            self.scheduler.observe_rate(self.stats.steps / self.stats.wall_s)
        return self.scheduler.completed

    def tick(self) -> None:
        """One serving iteration: request-level joins (admission against pool
        pressure, prefill into owned pages), then ONE decode launch over the
        live rows. Public so arrival-driven loops (``launch/serve.py
        --arrival-rate``, ``benchmarks/serving_load.py``) can interleave
        submissions with ticks on the wall clock."""
        now = time.perf_counter()
        tr = self._tr
        admitted = self.scheduler.admit(now, pool=self.pool)
        if tr is not None:
            for req in admitted:
                # lane phase 1: submission -> admission (queueing delay)
                tr.complete("queued", "request", req.submitted_at, now,
                            lane=req.uid, args={"prompt": len(req.prompt)})
        for req, logits, row_state in self._prefill_admitted(admitted):
            if self.pool is not None:
                self._account_pages(self.pool.ensure(req.uid, len(req.prompt)))
                self._splice_row_paged(req.uid, row_state)
            else:
                self._splice_row(req.slot, row_state)
            self.lengths[req.slot] = len(req.prompt)
            if self._sampled and self._paged:
                # per-request position-keyed device draw: the first token is
                # keyed at the last PROMPT position, so it is identical
                # whenever/wherever this request is admitted
                tok = int(np.asarray(self._sample_fn(
                    jnp.asarray(np.asarray(logits).reshape(1, -1)),
                    jnp.asarray(self._request_key(req))[None, :],
                    jnp.int32(len(req.prompt) - 1),
                ))[0])
            else:
                tok = int(self.sampler(np.asarray(logits))[0])
            self.next_token[req.slot] = tok
            self.active[req.slot] = True
            self.stats.tokens += len(req.prompt)
            # first sampled token may already finish the request
            self.scheduler.step_done(req.slot, tok, now, self.eos)
            if tr is not None:
                # lane phase 2: admission -> spliced + first token sampled
                tr.complete("prefill", "request", req.admitted_at,
                            time.perf_counter(), lane=req.uid,
                            args={"prompt": len(req.prompt)})
            if req.done:
                self.active[req.slot] = False
                self._release_request(req)
        if not self.scheduler.running:
            return
        if self._paged:
            self._tick_paged()
            return
        # group-tick path (recurrent archs / paged=False): per-row learned
        # speculative lengths — the tick self-drafts as far as the
        # slowest-adapting ACTIVE row allows (windows are batch-wide
        # programs; acceptance and KV rollback are per-row)
        k_tick = 1
        if self._spec_ok:
            k_tick = min(
                self.scheduler.spec_len(s) for s in self.scheduler.running
            )
            k_tick = max(1, min(k_tick, self._spec_cap_eff))
        if k_tick > 1:
            self._tick_window(k_tick)
        else:
            self._tick_single()

    # ------------------------------------------------------------------
    def _tick_paged(self) -> None:
        """One continuous-batching window over the paged pool.

        The live rows (whatever requests are running right now) pack into a
        power-of-two rows bucket and run ONE compiled window launch — pad
        rows carry all-zero page tables (writes land in the scratch page) and
        zero lengths/tokens, and are masked out of acceptance, rotation and
        the predictor EMA via ``accepted = 0``. Window length: 1 when
        speculation is off (a plain tick is a size-1 window; sampling at
        temperature > 0 draws from the window's f32 last-position logits,
        a lossless upcast), else the slowest live row's learned spec length.

        Per-row acceptance mirrors the group-tick window: commit up to (not
        past) the first residency miss, clamped >= 1 (serving drops missed
        experts in-step; no replay path); rejected suffixes roll the row's
        PAGES back via the paged snapshot/rollback and re-draft next window
        after rotation has corrected residency. Rows that finish mid-window
        release their pages before the next admission runs.
        """
        sch = self.scheduler
        live = [s for s in sorted(sch.running) if self.active[s]]
        if not live:
            return
        tr = self._tr
        t_tick = time.perf_counter()
        if tr is not None:
            tr.new_unit("tick")
        k = 1
        if self._spec_ok:
            k = min(sch.spec_len(s) for s in live)
            k = max(1, min(k, self._spec_cap_eff))
        # grow each live row's page table to cover the window's writes — the
        # admission reservation sized this worst-case, so ensure cannot fail
        for s in live:
            self._account_pages(
                self.pool.ensure(sch.running[s].uid, int(self.lengths[s]) + k)
            )
        rows = 1 << max(0, len(live) - 1).bit_length()   # pow2 bucket >= live
        pt = np.zeros((rows, self.pool.row_pages), np.int32)
        tok = np.zeros((rows,), np.int32)
        lens = np.zeros((rows,), np.int32)
        keys = None
        if self._sampled:
            keys_np = np.zeros((rows, 2), np.uint32)
        for i, s in enumerate(live):
            pt[i] = self.pool.table_array(sch.running[s].uid)
            tok[i] = self.next_token[s]
            lens[i] = self.lengths[s]
            if self._sampled:
                # request-intrinsic base keys: the row's draws depend only on
                # (its seed, its cache positions), never its slot or the
                # window's other occupants — CB streams == isolated streams
                keys_np[i] = self._request_key(sch.running[s])
        if self._sampled:
            keys = jnp.asarray(keys_np)
        if tr is not None:
            # every physical page this window will read/write, for the
            # auditor's use-after-release replay
            tr.instant("kv_use", "kv_pool", args={
                "pages": sorted({int(p) for row in pt[: len(live)]
                                 for p in row if p}),
                "rows": len(live),
            })
        step_fn, snap_fn, roll_fn = self._window_fns(k)
        residency = None
        if self.res_mgr is not None:
            residency = self.res_mgr.stacked_residency()
        pt_j = jnp.asarray(pt)
        lens_j = jnp.asarray(lens)
        saved = None
        if self.res_mgr is not None:
            # pre-window page contents: misses may reject per-row suffixes.
            # Dispatched BEFORE the donating window step, so it reads the
            # pre-window planes.
            saved = snap_fn(self.pool_state, lens_j, pt_j)
            self.stats.device_dispatches += 1
            if tr is not None:
                tr.instant("kv_snapshot", "kv_pool", args={"rows": len(live)})
        if tr is not None:
            t_launch = time.perf_counter()
        draft, last_logits, self.pool_state, aux = step_fn(
            self.params, self._routers_next, jnp.asarray(tok),
            self.pool_state, lens_j, residency, pt_j, rng_keys=keys,
        )
        if tr is not None:
            tr.complete("launch", "launch", t_launch, time.perf_counter(),
                        args={"rows": len(live), "k": k})
        self.stats.device_dispatches += 1
        self.stats.windows += 1
        if k > 1:
            self.stats.spec_windows += 1
        if self._sampled:
            # the per-position warped distributions (draft AND verifier for a
            # self-drafting window) ride the same async channel as the route
            # telemetry; the stochastic accept rule runs on them below
            aux["sample_probs"].copy_to_host_async()
            self.stats.overlapped_pulls += 1
        if self.res_mgr is not None:
            for key, v in aux.items():
                if key.startswith("route_") or key == "demand_next":
                    v.copy_to_host_async()
                    self.stats.overlapped_pulls += 1
            if self.prefetch:
                # window still in flight: ship the predicted boundary's
                # uploads into the shadow generation under it (request joins
                # between ticks just drift the shadow — the next commit's
                # catch-up copies reconcile it)
                self.res_mgr.begin_prefetch(self.predictor)
        if tr is not None:
            t_pull = time.perf_counter()
        # greedy AND sampled windows draft on-device: [K, rows], THE
        # queue-draining pull (sampled drafting happened in-graph from the
        # warped per-position distributions, keyed per request)
        draft_np = np.asarray(draft)
        if tr is not None:
            tr.complete("pull", "pull", t_pull, time.perf_counter(),
                        args={"rows": len(live), "k": k})
        self.stats.sync_pulls += 1
        accepted = np.zeros((rows,), np.int32)
        accepted[: len(live)] = k
        miss = None
        if self.res_mgr is not None:
            miss = concat_route_telemetry(aux, "miss", self._moe_segs, axis=1)
            step_row_miss = miss.any(axis=(1, 3))               # [K, rows]
            any_miss = step_row_miss.any(axis=0)
            first = np.where(any_miss, step_row_miss.argmax(axis=0), k)
            accepted[: len(live)] = np.maximum(first[: len(live)], 1)
            if tr is not None and bool(any_miss[: len(live)].any()):
                tr.instant("miss", "launch", args={
                    "rows": int(any_miss[: len(live)].sum()), "k": k,
                })
        if self._sampled:
            # stochastic accept over the pulled distributions. Self-drafting
            # passes the SAME array as p and q (ratio exactly 1), so the rule
            # accepts every position and the resample swap below is dormant —
            # it is the live plug point for a real p != q drafter, and it
            # composes with the miss cap by per-row min (a miss below the
            # first stochastic rejection wins, and then the swapped token is
            # never fed)
            probs = np.asarray(aux["sample_probs"])         # [K, rows, V]
            s_acc, resampled = stochastic_accept(
                draft_np, probs, probs, self._accept_rng
            )
            stoch = np.where(s_acc < k, s_acc + 1, k).astype(np.int32)
            rej = np.flatnonzero(s_acc < k)
            if rej.size:
                draft_np = draft_np.copy()      # device pull may be read-only
                draft_np[s_acc[rej], rej] = resampled[rej]
            accepted[: len(live)] = np.minimum(
                accepted[: len(live)], stoch[: len(live)]
            )
        # a finishing row commits only what it can still emit; ``offered`` =
        # drafts the row could have used (the accept-rate denominator, so
        # unused tail drafts don't read as rejections)
        offered: Dict[int, int] = {}
        for i, s in enumerate(live):
            req = sch.running[s]
            budget = req.max_new - len(req.output)
            offered[s] = min(k, budget)
            accepted[i] = min(int(accepted[i]), budget)
        if saved is not None and (accepted[: len(live)] < k).any():
            self.pool_state = roll_fn(
                self.pool_state, saved, lens_j, jnp.asarray(accepted), pt_j
            )
            self.stats.device_dispatches += 1
            if tr is not None:
                tr.instant("kv_rollback", "kv_pool", args={
                    "accepted": [int(a) for a in accepted[: len(live)]],
                })
        now = time.perf_counter()
        fed_total = 0
        k_committed = 0
        for i, s in enumerate(live):
            a = int(accepted[i])
            self.lengths[s] += a
            k_committed = max(k_committed, a)
            req = sch.running[s]
            fed = 0
            for j in range(a):
                t = int(draft_np[j, i])
                self.next_token[s] = t
                sch.step_done(s, t, now, self.eos)
                fed += 1
                if tr is not None:
                    tr.instant("token", "request", lane=req.uid,
                               args={"tok": t})
                if req.done:
                    self.active[s] = False
                    self._release_request(req)
                    break
            fed_total += fed
            sch.observe_accept(s, offered[s], fed)
            if k > 1:
                self.stats.drafted_tokens += offered[s]
                self.stats.accepted_tokens += fed
        # 'steps' = sequential decode positions the window committed
        self.stats.steps += k_committed
        self.stats.tokens += fed_total
        if self.res_mgr is not None:
            # pad rows and rejected suffixes are masked out of the hit/miss
            # accounting and the demand-predictor EMA by accepted=[rows]
            self.res_mgr.rotate_window_from_telemetry(
                self.predictor,
                concat_route_telemetry(aux, "ids", self._moe_segs, axis=1),
                concat_route_telemetry(aux, "weights", self._moe_segs, axis=1),
                miss,
                np.asarray(aux["demand_next"]),
                accepted=accepted,
            )
        self.metrics.histogram(
            "window_ms", "wall ms per serving window"
        ).observe((time.perf_counter() - t_tick) * 1e3)

    # ------------------------------------------------------------------
    def _tick_single(self) -> None:
        """Group-tick single-token decode (recurrent archs / ``paged=False``):
        one fused ``decode_model`` step over the fixed contiguous batch."""
        tr = self._tr
        if tr is not None:
            tr.new_unit("tick")
            t_launch = time.perf_counter()
        residency = None
        if self.res_mgr is not None:
            residency = self.res_mgr.stacked_residency()
        logits, self.state, aux = self._decode(
            self.params,
            self._routers_next,
            jnp.asarray(self.next_token),
            self.state,
            jnp.asarray(self.lengths),
            residency,
        )
        if tr is not None:
            tr.complete("launch", "launch", t_launch, time.perf_counter())
        self.stats.device_dispatches += 1
        if self.res_mgr is not None:
            # start D2H copies of the routing/demand telemetry now: they
            # complete while the host samples, so the between-step rotation
            # reads below never drain the device queue
            for k, v in aux.items():
                if k.startswith("route_") or k == "demand_next":
                    v.copy_to_host_async()
                    self.stats.overlapped_pulls += 1
        if tr is not None:
            t_pull = time.perf_counter()
        logits_np = np.asarray(logits)
        if tr is not None:
            tr.complete("pull", "pull", t_pull, time.perf_counter())
        self.stats.sync_pulls += 1
        self.lengths += self.active
        toks = self.sampler(logits_np)
        now = time.perf_counter()
        for slot in list(self.scheduler.running.keys()):
            self.next_token[slot] = toks[slot]
            self.scheduler.step_done(slot, toks[slot], now, self.eos)
            if slot in self.scheduler.free_slots:
                self.active[slot] = False
            if self._spec_ok:
                # a plain tick is a size-1 window that accepted its token:
                # feedback that lets a fresh row's spec length grow
                self.scheduler.observe_accept(slot, 1, 1)
        self.stats.steps += 1
        self.stats.tokens += int(self.active.sum())
        if self.res_mgr is not None:
            self._rotate_from_aux(aux)

    # ------------------------------------------------------------------
    def _tick_window(self, k: int) -> None:
        """One speculative group tick: ``k`` self-drafted positions for the
        whole contiguous batch in ONE compiled program.

        Per-row acceptance: a row commits drafted tokens up to (but not past)
        its first residency miss — clamped to >= 1, since position 0 is
        exactly what a plain tick would have computed (serving drops missed
        experts in-step; it has no replay path). Rejected positions roll the
        row's KV slots back (``tfm.rollback_kv_window`` takes per-row keep
        counts for the ragged batch) and re-draft next window, after rotation
        has had a chance to fix residency. Accept outcomes feed the
        scheduler's per-row speculative lengths.
        """
        tr = self._tr
        if tr is not None:
            tr.new_unit("tick")
        step_fn, snap_fn, roll_fn = self._window_fns(k)
        residency = None
        if self.res_mgr is not None:
            residency = self.res_mgr.stacked_residency()
        lengths = jnp.asarray(self.lengths)
        saved = None
        if self.res_mgr is not None:
            # pre-window KV slot contents: misses may reject per-row suffixes
            saved = snap_fn(self.state, lengths)
            self.stats.device_dispatches += 1
            if tr is not None:
                tr.instant("kv_snapshot", "kv_pool")
        if tr is not None:
            t_launch = time.perf_counter()
        draft, _logits, self.state, aux = step_fn(
            self.params, self._routers_next,
            jnp.asarray(self.next_token), self.state, lengths, residency,
        )
        if tr is not None:
            tr.complete("launch", "launch", t_launch, time.perf_counter(),
                        args={"k": k})
        self.stats.device_dispatches += 1
        self.stats.spec_windows += 1
        if self.res_mgr is not None:
            for key, v in aux.items():
                if key.startswith("route_") or key == "demand_next":
                    v.copy_to_host_async()
                    self.stats.overlapped_pulls += 1
        if tr is not None:
            t_pull = time.perf_counter()
        draft_np = np.asarray(draft)           # [K, B]: THE queue-draining pull
        if tr is not None:
            tr.complete("pull", "pull", t_pull, time.perf_counter(),
                        args={"k": k})
        self.stats.sync_pulls += 1
        accepted = np.where(self.active, k, 0).astype(np.int32)
        miss = None
        if self.res_mgr is not None:
            miss = concat_route_telemetry(aux, "miss", self._moe_segs, axis=1)
            step_row_miss = miss.any(axis=(1, 3))               # [K, B]
            any_miss = step_row_miss.any(axis=0)
            first = np.where(any_miss, step_row_miss.argmax(axis=0), k)
            accepted = np.where(
                self.active, np.maximum(first, 1), 0
            ).astype(np.int32)
            if tr is not None and bool((any_miss & self.active).any()):
                tr.instant("miss", "launch", args={
                    "rows": int((any_miss & self.active).sum()), "k": k,
                })
        # a finishing row commits only what it can still emit: drafting past
        # max_new must not advance lengths or count as accepted throughput.
        # ``offered`` = drafts the row could have used — the accept-rate
        # denominator, so unused tail drafts don't read as rejections
        offered: Dict[int, int] = {}
        for slot, req in self.scheduler.running.items():
            if self.active[slot]:
                budget = req.max_new - len(req.output)
                offered[slot] = min(k, budget)
                accepted[slot] = min(int(accepted[slot]), budget)
        if saved is not None and (accepted < k).any():
            self.state = roll_fn(
                self.state, saved, lengths, jnp.asarray(accepted)
            )
            self.stats.device_dispatches += 1
            if tr is not None:
                tr.instant("kv_rollback", "kv_pool")
        self.lengths += accepted
        now = time.perf_counter()
        fed_total = 0
        for slot in list(self.scheduler.running.keys()):
            if not self.active[slot]:
                continue
            a = int(accepted[slot])
            fed = 0
            for j in range(a):
                tok = int(draft_np[j, slot])
                self.next_token[slot] = tok
                self.scheduler.step_done(slot, tok, now, self.eos)
                fed += 1
                if slot in self.scheduler.free_slots:
                    self.active[slot] = False
                    break
            fed_total += fed
            self.scheduler.observe_accept(slot, offered[slot], fed)
            self.stats.drafted_tokens += offered[slot]
            self.stats.accepted_tokens += fed
        # 'steps' = sequential decode positions the batch committed (what the
        # scheduler's tokens-per-row admission rate is derived from), not the
        # k positions the program speculated over
        self.stats.steps += int(accepted.max(initial=0))
        self.stats.tokens += fed_total
        if self.res_mgr is not None:
            # rejected positions re-decode next window and are recorded THEN:
            # per-row accepted counts mask them out of the hit/miss accounting
            # and the demand-predictor EMA here
            self.res_mgr.rotate_window_from_telemetry(
                self.predictor,
                concat_route_telemetry(aux, "ids", self._moe_segs, axis=1),
                concat_route_telemetry(aux, "weights", self._moe_segs, axis=1),
                miss,
                np.asarray(aux["demand_next"]),
                accepted=accepted,
            )

    # ------------------------------------------------------------------
    def _rotate_from_aux(self, aux: Dict[str, jax.Array]) -> None:
        """Between-step rotation from routing telemetry: assemble the step's
        [L, ...] arrays and hand off to the manager's shared helper (the
        demand GEMM already ran on device — ``aux["demand_next"]``)."""
        self.res_mgr.rotate_from_telemetry(
            self.predictor,
            concat_route_telemetry(aux, "ids", self._moe_segs),
            concat_route_telemetry(aux, "weights", self._moe_segs),
            concat_route_telemetry(aux, "miss", self._moe_segs),
            np.asarray(aux["demand_next"]),
        )

    # ------------------------------------------------------------------
    def latency_summary(self) -> Dict[str, float]:
        """TTFT + inter-token latency percentiles over COMPLETED requests
        (the load-generator's goodput rows; wall-clock, so only meaningful
        when requests were submitted at their real arrival times).

        Backed by the metrics registry: the ``ttft_ms`` / ``itl_ms``
        histograms are rebuilt from the scheduler's completed set on every
        call (reset + re-observe keeps the call idempotent), then read back
        via :meth:`Histogram.percentile` — raw samples are retained, so the
        numbers match the legacy ``np.percentile`` output exactly. The same
        histograms feed the Prometheus exposition (``--metrics-port``)."""
        done = self.scheduler.completed
        ttft = self.metrics.histogram("ttft_ms", "time to first token (ms)")
        itl = self.metrics.histogram("itl_ms", "inter-token latency (ms)")
        ttft.reset()
        itl.reset()
        for r in done:
            if r.first_token_at:
                ttft.observe(1e3 * (r.first_token_at - r.submitted_at))
            ts = r.token_times
            for a, b in zip(ts, ts[1:]):
                itl.observe(1e3 * (b - a))
        return {
            "completed": len(done),
            "ttft_p50_ms": round(ttft.percentile(50), 3),
            "ttft_p99_ms": round(ttft.percentile(99), 3),
            "itl_p50_ms": round(itl.percentile(50), 3),
            "itl_p99_ms": round(itl.percentile(99), 3),
        }

    def summary(self) -> Dict[str, float]:
        """Engine stats + request-latency percentiles in one dict."""
        out = self.stats.summary()
        out.update(self.latency_summary())
        return out

    def metrics_registry(self) -> "MetricsRegistry":
        """Refresh and return the registry for Prometheus scrapes: rebuilds
        the latency histograms and mirrors the aggregate ``EngineStats``
        counters into ``engine_*`` gauges (called per scrape by
        ``serve.py --metrics-port``)."""
        self.latency_summary()
        self.metrics.set_from(self.stats.summary())
        return self.metrics
