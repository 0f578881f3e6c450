"""Continuous batching over the paged KV pool.

Exactness contract: with CB on, every request's emitted tokens are
bit-identical to running that request alone at batch-1 — across residency
regimes, with speculative windows, through page recycling, and on quantized
slot formats. Plus pool accounting invariants, dispatch-count bounds, and
the request-lifecycle telemetry.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import params_for
from repro.config import ResidencyConfig
from repro.config.base import AttentionConfig
from repro.models import attention as attn
from repro.models import transformer as tfm
from repro.models.transformer import Runtime
from repro.serving import ServingEngine
from repro.serving.kv_pool import KVPagePool, PagePoolError
from repro.serving.scheduler import Scheduler


# ===========================================================================
# paged device layout: bitwise equality with the contiguous cache
# ===========================================================================
def test_paged_attention_bitwise_equals_contiguous(rng):
    """attention_decode through a PERMUTED page table over shared planes is
    bit-identical to the contiguous [B, cap, ...] cache holding the same
    logical KV — off-table pages hold huge garbage to prove masked positions
    contribute exactly +-0.0."""
    acfg = AttentionConfig(num_heads=4, num_kv_heads=2, head_dim=8)
    d_model = 32
    p = attn.init_attention(jax.random.PRNGKey(0), d_model, acfg, jnp.float32)
    b, cap, ps = 3, 16, 4
    n_pp = cap // ps
    P = 14                                     # physical pages incl. scratch 0
    cl = np.asarray([5, 9, 0], np.int32)       # ragged lengths, one empty row
    x = rng.standard_normal((b, 1, d_model)).astype(np.float32)
    ck = rng.standard_normal((b, cap, 2, 8)).astype(np.float32)
    cv = rng.standard_normal((b, cap, 2, 8)).astype(np.float32)
    y_ref, cache_ref = attn.attention_decode(
        p, acfg, jnp.asarray(x), {"k": jnp.asarray(ck), "v": jnp.asarray(cv)},
        jnp.asarray(cl),
    )
    perm = rng.permutation(np.arange(1, P))[: b * n_pp].reshape(b, n_pp)
    perm = perm.astype(np.int32)
    pk = rng.standard_normal((P, ps, 2, 8)).astype(np.float32) * 1e3
    pv = rng.standard_normal((P, ps, 2, 8)).astype(np.float32) * 1e3
    for i in range(b):
        for j in range(n_pp):
            pk[perm[i, j]] = ck[i, j * ps:(j + 1) * ps]
            pv[perm[i, j]] = cv[i, j * ps:(j + 1) * ps]
    y_pg, cache_pg = attn.attention_decode(
        p, acfg, jnp.asarray(x), {"k": jnp.asarray(pk), "v": jnp.asarray(pv)},
        jnp.asarray(cl), page_table=jnp.asarray(perm),
    )
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_pg))
    # the new KV landed at the right physical (page, offset) per row
    for i in range(b):
        s = cl[i] % cap
        pg, off = perm[i, s // ps], s % ps
        np.testing.assert_array_equal(
            np.asarray(cache_ref["k"])[i, s], np.asarray(cache_pg["k"])[pg, off]
        )
        np.testing.assert_array_equal(
            np.asarray(cache_ref["v"])[i, s], np.asarray(cache_pg["v"])[pg, off]
        )


def test_paged_snapshot_rollback_restores_pages(rng):
    """Paged KV snapshot/rollback: per-row keep counts restore exactly the
    rejected window slots at their page-table addresses."""
    class StubCfg:
        segments = ((("attn_moe",), 2), (("attn_mlp",), 1))

    cfg = StubCfg()
    b, cap, ps = 3, 16, 4
    n_pp = cap // ps
    P = 14
    k_steps = 3
    cl = np.asarray([5, 9, 0], np.int32)
    perm = rng.permutation(np.arange(1, P))[: b * n_pp].reshape(b, n_pp)
    pt = jnp.asarray(perm.astype(np.int32))

    def plane(reps):
        return {
            "k": jnp.asarray(rng.standard_normal((reps, P, ps, 2, 8)),
                             jnp.float32),
            "v": jnp.asarray(rng.standard_normal((reps, P, ps, 2, 8)),
                             jnp.float32),
        }

    state = ((plane(2),), (plane(1),))
    before = np.asarray(state[0][0]["k"])
    saved = tfm.snapshot_kv_window(cfg, state, jnp.asarray(cl), k_steps,
                                   page_table=pt)
    garbled = jax.tree.map(lambda c: c.at[:].add(7.0), state)
    keep = np.asarray([1, 0, 3], np.int32)
    rolled = tfm.rollback_kv_window(cfg, garbled, saved, jnp.asarray(cl),
                                    k_steps, jnp.asarray(keep), page_table=pt)
    after = np.asarray(rolled[0][0]["k"])
    garb = np.asarray(garbled[0][0]["k"])
    for i in range(b):
        for j in range(k_steps):
            s = (cl[i] + j) % cap
            pg, off = perm[i, s // ps], s % ps
            want = garb[:, pg, off] if j < keep[i] else before[:, pg, off]
            np.testing.assert_array_equal(after[:, pg, off], want)


# ===========================================================================
# pool accounting
# ===========================================================================
def test_kv_pool_reserve_ensure_release_invariants(rng):
    """Seeded random join/leave churn: no page is ever leaked, double-handed,
    or drawn past its reservation (tier-1 mirror of the hypothesis suite)."""
    pool = KVPagePool(num_pages=12, page_size=4, row_pages=4)
    live = {}
    uid = 0
    for _ in range(300):
        op = rng.integers(0, 3)
        if op == 0:                                     # admit
            need = int(rng.integers(1, pool.row_pages + 1))
            if pool.reserve(uid, need):
                live[uid] = need
                pool.ensure(uid, int(rng.integers(1, need * pool.page_size + 1)))
            else:
                assert need > pool.pages_reservable
            uid += 1
        elif op == 1 and live:                          # grow a live request
            u = int(rng.choice(list(live)))
            pool.ensure(u, int(rng.integers(1, live[u] * pool.page_size + 1)))
        elif op == 2 and live:                          # finish
            u = int(rng.choice(list(live)))
            freed = pool.release(u)
            assert freed <= live.pop(u)
        pool.check()
        assert pool.pages_in_use + pool.pages_free == pool.num_pages
    for u in list(live):
        pool.release(u)
    pool.check()
    assert pool.pages_free == pool.num_pages


def test_kv_pool_ensure_past_reservation_raises():
    pool = KVPagePool(num_pages=8, page_size=4, row_pages=4)
    assert pool.reserve(7, 2)
    with pytest.raises(PagePoolError):
        pool.ensure(7, 3 * pool.page_size)              # needs 3 > reserved 2
    # reservations gate admission, not the free list: 6 pages are still free
    # but only 8 - 2 = 6 ... of which the backlog holds 2
    assert pool.pages_free == 8 and pool.pages_reservable == 6
    assert not pool.reserve(8, 7)
    assert pool.reserve(8, 6)


# ===========================================================================
# continuous batching exactness (the PR contract)
# ===========================================================================
def _f32_params(arch):
    import dataclasses

    from repro.models import init_params

    cfg = dataclasses.replace(params_for(arch)[0], dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _serve(cfg, params, prompts, *, num_slots, max_new=5, cache_len=32,
           rescfg=None, spec_cap=4, seeds=None, **kw):
    eng = ServingEngine(
        cfg, params, rt=Runtime(cache_len=cache_len), num_slots=num_slots,
        residency=rescfg, spec_cap=spec_cap, **kw,
    )
    seeds = seeds or [None] * len(prompts)
    reqs = [eng.submit(p, max_new=max_new, seed=s)
            for p, s in zip(prompts, seeds)]
    eng.run()
    return eng, [r.output for r in reqs]


@pytest.mark.parametrize("regime", ["full", "rotary_hi", "rotary_hi_int4"])
def test_cb_concurrent_matches_isolated(rng, regime):
    """Concurrent requests through the paged window == each request alone at
    batch-1, with spec windows on, under full residency, prefetch-covered
    rotary, and a quantized slot format (miss-free regimes: the residency
    trajectory is request-independent, so bit-identity must hold)."""
    cfg, params = params_for("qwen2-moe-a2.7b")
    e = cfg.moe.num_experts

    def mk_res():
        if regime == "full":
            return None
        quant = "int4" if regime.endswith("int4") else None
        return ResidencyConfig(mode="rotary", num_slots=e, quantization=quant)

    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (5, 8, 11)]
    eng, outs = _serve(cfg, params, prompts, num_slots=3, rescfg=mk_res())
    assert eng.pool is not None and eng.stats.windows > 0
    if regime != "full":
        assert eng.stats.misses == 0                    # prefetch covers
    for i, p in enumerate(prompts):
        _, ref = _serve(cfg, params, [p], num_slots=1, rescfg=mk_res())
        assert outs[i] == ref[0], (regime, i)


@pytest.mark.parametrize("regime", ["full", "rotary_hi"])
def test_cb_sampled_matches_isolated(rng, regime):
    """Temperature > 0 serving: each request's PRNG stream is keyed on its
    OWN seed and position (never batch composition), so a sampled request
    under continuous batching emits the same tokens as running alone —
    including through speculative windows whose rejected drafts re-draw the
    same positions with the same fold_in keys. Scoped to the f32 miss-free
    regimes: int4 dequant differs sub-ULP across row-bucket batch shapes,
    which greedy argmax absorbs but a categorical draw can flip — and in
    float32, since bf16 logits can differ by an ULP across row buckets."""
    from repro.serving.sampler import SamplerConfig

    cfg, params = _f32_params("qwen2-moe-a2.7b")
    e = cfg.moe.num_experts
    mk_res = lambda: (None if regime == "full" else
                      ResidencyConfig(mode="rotary", num_slots=e))
    smp = lambda: SamplerConfig(temperature=0.8, top_k=20, top_p=0.95, seed=3)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (5, 8, 11)]
    seeds = [11, 22, 33]
    eng, outs = _serve(cfg, params, prompts, num_slots=3, rescfg=mk_res(),
                       sampler=smp(), seeds=seeds)
    assert eng.stats.spec_windows > 0          # sampled serving still drafts
    for i, p in enumerate(prompts):
        _, ref = _serve(cfg, params, [p], num_slots=1, rescfg=mk_res(),
                        sampler=smp(), seeds=[seeds[i]])
        assert outs[i] == ref[0], (regime, i)
    # the stream is the seed's, not the slot's: re-serving concurrently with
    # the same seeds reproduces the outputs bitwise
    _, outs2 = _serve(cfg, params, prompts, num_slots=3, rescfg=mk_res(),
                      sampler=smp(), seeds=seeds)
    assert outs == outs2


def test_cb_sampled_slot_starved_single_request_exact(rng):
    """Sampled decode under a slot-starved rotary residency: a single request
    through the paged CB engine matches batch-1 bitwise even when stochastic
    rejection composes with residency-miss truncation on the same windows."""
    from repro.serving.sampler import SamplerConfig

    cfg, params = params_for("qwen2-moe-a2.7b")
    prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    res = lambda: ResidencyConfig(mode="rotary", num_slots=5)
    smp = lambda: SamplerConfig(temperature=0.9, seed=5)
    eng_cb, out_cb = _serve(cfg, params, [prompt], num_slots=4, rescfg=res(),
                            max_new=6, sampler=smp(), seeds=[17])
    _, out_iso = _serve(cfg, params, [prompt], num_slots=1, rescfg=res(),
                        max_new=6, sampler=smp(), seeds=[17])
    assert out_cb[0] == out_iso[0]
    assert eng_cb.stats.windows > 0


def test_cb_slot_starved_single_request_exact(rng):
    """Slot-starved rotary (misses are dropped in-step, so the residency
    trajectory is shared state between concurrent rows): a SINGLE request
    through the paged CB engine is still bit-identical to batch-1 — and to
    the pre-paging group-tick engine."""
    cfg, params = params_for("qwen2-moe-a2.7b")
    prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
    res = lambda: ResidencyConfig(mode="rotary", num_slots=5)
    eng_cb, out_cb = _serve(cfg, params, [prompt], num_slots=4, rescfg=res(),
                            max_new=6)
    _, out_iso = _serve(cfg, params, [prompt], num_slots=1, rescfg=res(),
                        max_new=6)
    _, out_legacy = _serve(cfg, params, [prompt], num_slots=1, rescfg=res(),
                           max_new=6, paged=False)
    assert out_cb[0] == out_iso[0] == out_legacy[0]
    assert eng_cb.stats.windows > 0


def test_cb_slot_starved_concurrent_completes(rng):
    """Concurrent slot-starved rotary can't be compared row-for-row against
    isolated runs (the rotation trajectory is shared), but every request must
    complete at full length with pages fully recycled and the drafted/accepted
    accounting consistent."""
    cfg, params = params_for("qwen2-moe-a2.7b")
    prompts = [rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(3)]
    eng, outs = _serve(cfg, params, prompts, num_slots=2,
                       rescfg=ResidencyConfig(mode="rotary", num_slots=5),
                       max_new=6)
    assert all(len(o) == 6 for o in outs)
    assert eng.stats.hits + eng.stats.misses > 0
    assert eng.stats.accepted_tokens <= eng.stats.drafted_tokens
    s = eng.stats
    assert s.kv_pages_released == s.kv_pages_allocated > 0


def test_slot_starved_admission_prefill_is_exact(rng):
    """Admission prefill under a rotary residency at its minimum slot count
    (the expert store stays in host memory) reruns each missed chunk until it
    is miss-free, so its logits and prompt KV equal the full-store prefill's
    in f32 — and the misses are counted."""
    from repro.serving.scheduler import Request

    cfg, params = _f32_params("qwen2-moe-a2.7b")
    s = 11
    prompt = rng.integers(0, cfg.vocab_size, s).astype(np.int32)
    full = ServingEngine(cfg, params, rt=Runtime(cache_len=32), num_slots=1)
    starved = ServingEngine(
        cfg, params, rt=Runtime(cache_len=32), num_slots=1,
        residency=ResidencyConfig(mode="rotary", num_slots=2 * cfg.moe.top_k),
    )
    [(_, lg_ref, st_ref)] = full._prefill_admitted([Request(0, prompt, 1)])
    [(_, lg, st)] = starved._prefill_admitted([Request(0, prompt, 1)])
    np.testing.assert_allclose(lg, np.asarray(lg_ref), rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st_ref)):
        np.testing.assert_allclose(np.asarray(a)[:, :, :s],
                                   np.asarray(b)[:, :, :s],
                                   rtol=1e-5, atol=1e-5)
    assert starved.stats.misses > 0 and starved.stats.relaunched_steps > 0
    assert starved.stats.prefill_chunks == 6        # 2-token chunks: 5 + 1


def test_cb_page_recycling_under_queueing_exact(rng):
    """A pool smaller than the request population forces queueing: later
    requests prefill into JUST-FREED garbage pages (LIFO reuse) and must
    still emit bit-identical tokens to running alone."""
    cfg, params = params_for("starcoder2-3b")
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9, 12, 7)]
    # 8 pages of 4 positions = ONE row's worth of KV for four requests:
    # each needs pages_for(prompt + max_new + spec_cap - 1) ~ 4 pages
    eng, outs = _serve(cfg, params, prompts, num_slots=4, cache_len=32,
                       kv_page_size=4, kv_pages=8)
    s = eng.stats
    assert s.kv_pages_hwm <= 8
    assert s.kv_pages_released == s.kv_pages_allocated > 0
    for i, p in enumerate(prompts):
        _, ref = _serve(cfg, params, [p], num_slots=1, cache_len=32,
                        kv_page_size=4, kv_pages=8)
        assert outs[i] == ref[0], i


def test_cb_dispatch_counts_dense(rng):
    """The 1-launch + 1-queue-draining-pull-per-window contract: on a dense
    arch (no snapshot/rollback) every decode launch is a window, every window
    drains the queue exactly once, and the only other launches are the
    per-join page splices."""
    cfg, params = params_for("starcoder2-3b")
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9)]
    eng, _ = _serve(cfg, params, prompts, num_slots=2, max_new=6)
    s = eng.stats
    assert s.windows > 0
    assert s.sync_pulls == s.windows
    assert s.device_dispatches == s.windows + len(prompts)


# ===========================================================================
# admission validation + request lifecycle telemetry
# ===========================================================================
def test_submit_validates_prompt_against_pool_capacity(rng):
    cfg, params = params_for("starcoder2-3b")
    eng = ServingEngine(cfg, params, rt=Runtime(cache_len=32), num_slots=2)
    with pytest.raises(ValueError, match="KV capacity"):
        eng.submit(rng.integers(0, cfg.vocab_size, 40), max_new=4)
    # queue-with-reason path: infeasible deadline is rejected with a reason
    r = eng.submit(rng.integers(0, cfg.vocab_size, 8), max_new=10_000,
                   deadline_s=1e-3)
    assert r.done and r.truncated and "infeasible" in r.reject_reason


def test_scheduler_pool_pressure_preserves_edf_order():
    """Admission stops at the first head-of-line request the pool cannot
    cover (no queue-jumping past EDF order), and resumes once pages free."""
    pool = KVPagePool(num_pages=4, page_size=4, row_pages=4)
    sch = Scheduler(num_slots=4, spec_cap=1)
    big = sch.submit(np.arange(12), max_new=4, now=0.0)     # needs 4 pages
    small = sch.submit(np.arange(2), max_new=2, now=0.0)    # needs 1 page
    assert sch.admit(0.0, pool=pool) == [big]
    assert sch.admit(0.0, pool=pool) == []                  # small must wait
    pool.ensure(big.uid, 12)
    for t in range(4):
        sch.step_done(big.slot, 1, now=float(t))
    pool.release(big.uid)
    assert sch.admit(5.0, pool=pool) == [small]
    assert small.admitted_at == 5.0


def test_request_lifecycle_timestamps_and_summary(rng):
    cfg, params = params_for("starcoder2-3b")
    eng = ServingEngine(cfg, params, rt=Runtime(cache_len=32), num_slots=2)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, 5), max_new=4)
            for _ in range(3)]
    eng.run()
    for r in reqs:
        assert r.submitted_at <= r.admitted_at <= r.first_token_at
        assert r.first_token_at <= r.finished_at
        assert len(r.token_times) == len(r.output) == 4
        assert all(a <= b for a, b in zip(r.token_times, r.token_times[1:]))
    summ = eng.summary()
    for key in ("ttft_p50_ms", "ttft_p99_ms", "itl_p50_ms", "itl_p99_ms",
                "windows", "kv_pages_hwm"):
        assert key in summ
    assert summ["completed"] == 3
    assert summ["ttft_p99_ms"] >= summ["ttft_p50_ms"] >= 0.0


def test_warmup_precompiles_without_changing_outputs(rng):
    cfg, params = params_for("starcoder2-3b")
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9)]
    eng = ServingEngine(cfg, params, rt=Runtime(cache_len=32), num_slots=2)
    assert eng.warmup(max_prompt_len=9) > 0
    reqs = [eng.submit(p, max_new=4) for p in prompts]
    eng.run()
    _, ref = _serve(cfg, params, prompts, num_slots=2, max_new=4)
    assert [r.output for r in reqs] == ref


def test_warmup_precompiles_resident_prefill(rng):
    """Under a rotating residency warmup compiles every admission chunk shape
    the envelope reaches, so serving then compiles no prefill program, and
    the outputs match an engine that was not warmed."""
    cfg, params = params_for("qwen2-moe-a2.7b")
    res = lambda: ResidencyConfig(mode="rotary", num_slots=5)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9)]
    eng = ServingEngine(cfg, params, rt=Runtime(cache_len=32), num_slots=2,
                        residency=res(), spec_cap=4)
    assert eng.warmup(max_prompt_len=9) > 0
    shapes = set(eng._resident_prefill_cache)
    reqs = [eng.submit(p, max_new=5) for p in prompts]
    eng.run()
    assert set(eng._resident_prefill_cache) == shapes
    _, ref = _serve(cfg, params, prompts, num_slots=2, rescfg=res())
    assert [r.output for r in reqs] == ref


# ===========================================================================
# asynchronous prefetch on the CB tick: shadow generations over the pool
# ===========================================================================
def test_cb_prefetch_matches_sync(rng):
    """The paged CB tick with prefetch=True (shadow-generation uploads under
    the in-flight window, boundary confirm/correct/flip at margin 0) emits
    bit-identical tokens to the synchronous-rotation engine on the same
    trace — prefetch-covered AND slot-starved f32 (host corrections are
    bitwise against device compute at f32)."""
    cfg, params = _f32_params("qwen2-moe-a2.7b")
    e = cfg.moe.num_experts
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (5, 9, 7)]
    starved = None
    for slots in (e, 5):
        res = lambda: ResidencyConfig(mode="rotary", num_slots=slots)
        _, ref = _serve(cfg, params, prompts, num_slots=3, rescfg=res())
        eng, got = _serve(cfg, params, prompts, num_slots=3, rescfg=res(),
                          prefetch=True)
        assert got == ref, slots
        starved = eng
    # the starved engine really rotated through the shadow protocol: slot
    # uploads happened and the boundary accounting ran
    assert starved.stats.hits + starved.stats.misses > 0
    assert starved.stats.bytes_uploaded > 0


def test_serving_prefetch_flag_validation(rng):
    """Loud errors for serving combos with nothing to prefetch."""
    cfg, params = params_for("qwen2-moe-a2.7b")
    e = cfg.moe.num_experts
    rt = lambda: Runtime(cache_len=32)
    with pytest.raises(ValueError, match="rotating"):
        ServingEngine(cfg, params, rt=rt(), num_slots=2, prefetch=True)
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(cfg, params, rt=rt(), num_slots=2, paged=False,
                      residency=ResidencyConfig(mode="rotary", num_slots=e),
                      prefetch=True)
    with pytest.raises(ValueError, match="reactive"):
        ServingEngine(cfg, params, rt=rt(), num_slots=2,
                      residency=ResidencyConfig(mode="lru", num_slots=e),
                      prefetch=True)
