"""Decode hot-path micro-benchmark: fused whole-stack step vs per-layer paths
vs speculative multi-token windows.

The decode paths of the SAME engine are compared on the ``qwen2_moe_a2_7b``
reduced config:

* ``seed``  — seed-style per-layer walk (``host_routing=True``: blocking
  logits pull + numpy softmax/top-k + per-layer LUT re-upload);
* ``layer`` — PR-1 device-resident per-layer hot path (``fused_decode=False``:
  2 jitted halves per MoE layer, async telemetry, one logits pull per token);
* ``fused`` — ONE compiled whole-stack step per token (donated KV state,
  on-device demand prediction, batched slot uploads);
* ``spec[K]`` — speculative self-drafting windows on the fused step: K tokens
  per compiled launch and per queue-draining pull, rotation at window
  boundaries (``--spec-k`` grows the row family);
* ``@int8 / @int4`` — quantized slot-store row family (``--quantization``):
  the fused and spec-4 paths re-run with int8 / grouped-int4 slots so the
  f16-vs-int8-vs-int4 link traffic (MB/token) is visible side by side;
* ``*_pf`` — asynchronous-prefetch row family (``prefetch=True``): the same
  fused / spec-4 rows with double-buffered slot planes — predicted uploads
  ship into a shadow generation while the live window computes, the boundary
  is a pointer flip plus a correction pass, and misses re-launch the ONE
  compiled step instead of paying the per-layer suffix replay;
* ``*_t`` — sampled row family (temperature 0.8, top-k 20, top-p 0.95): the
  fused single-token and spec-4 paths re-run drawing from the warped
  distribution with position-keyed PRNG streams and stochastic speculative
  acceptance — same compiled window family, exactness now distributional
  (and bitwise between the two rows, which share one seeded stream).

Acceptance checks: (a) greedy tokens IDENTICAL across all paths under every
residency mode (misses replay-corrected exactly; spec windows roll back +
replay), (b) accounting mechanism intact (every counted miss host-corrected;
same number of routed assignments), (c) miss-free fused decode issues exactly
ONE queue-draining device->host pull AND one compiled-program launch per token
— and miss-free spec-K decode exactly 1/K of each, (d) the fused step beats
the per-layer hot path >= 1.3x miss-free, and spec-4 beats the fused
single-token path >= 1.2x miss-free, (e) greedy self-drafting accepts every
drafted token miss-free (accept_rate >= 1.0 — the KV-rollback canary),
(f) quantized decode is exactness-clean WITHIN its format — greedy tokens
bit-identical between full residency, rotary, and rotary+spec-4 under int8
and int4 alike (host corrections run against the dequantized weights) — and
the int4 store moves <= 0.30x the f16 bytes per rotated expert,
(g) every prefetch row is bit-identical to its synchronous twin and the
miss-starved fused rotary row runs >= 1.5x faster with prefetch enabled,
with ``overlap_ms > 0`` recorded (uploads genuinely hid under compute),
(h) the sampled ``*_t`` rows emit bitwise-identical tokens (spec-4 sampled
== single-token sampled) with accept_rate on record, and sampled spec-4
beats sampled single-token >= 1.4x miss-free (the window also amortizes
the per-token host draw sync).

Run directly (``python -m benchmarks.decode_hot_path [--spec-k 2,4,8]
[--quantization int8,int4]``) or via ``python -m benchmarks.run`` /
``make bench-decode``; either way the row data lands in ``BENCH_decode.json``
so the perf trajectory accumulates across PRs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Dict, Sequence, Tuple

import jax
import numpy as np

PATHS = ("seed", "layer", "fused")


def _run_engine(cfg, params, mode: str, slots: int, path: str,
                prompt: np.ndarray, steps: int,
                quant: str | None = None, prefetch: bool = False,
                sampler=None) -> Dict:
    from repro.config import ResidencyConfig
    from repro.core import RotaryEngine
    from repro.models.transformer import Runtime

    spec_k = int(path[4:]) if path.startswith("spec") else 1
    eng = RotaryEngine(
        cfg, params,
        ResidencyConfig(mode=mode, num_slots=slots, quantization=quant),
        rt=Runtime(cache_len=max(128, prompt.shape[1] + steps + 8)),
        batch=prompt.shape[0],
        host_routing=(path == "seed"),
        fused_decode=None if path != "layer" else False,
        spec_k=spec_k,
        prefetch=prefetch,
    )
    if path == "fused" or spec_k > 1:
        assert eng._fused_decode, "fused path unexpectedly unavailable"
    # warmup: populate the jit caches so the timed loop measures steady state
    logits = eng.prefill(prompt)
    eng.decode(logits, 2, sampler=sampler)
    pulls0 = eng.stats.sync_pulls
    disp0 = eng.stats.device_dispatches
    bytes0 = eng.stats.bytes_uploaded
    # best-of-3 timing: single 16-step samples are noisy on a shared host and
    # this benchmark gates a >=1.3x acceptance; tokens from every repeat still
    # feed the cross-path identity check
    outs, walls = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        outs.append(eng.decode(eng.last_logits, steps, sampler=sampler))
        walls.append(time.perf_counter() - t0)
    timed = 3 * steps
    return {
        "engine": eng,
        "tokens": np.concatenate(outs, axis=1),
        "s_per_step": min(walls) / steps,
        "sync_pulls_per_step": (eng.stats.sync_pulls - pulls0) / timed,
        "dispatches_per_step": (eng.stats.device_dispatches - disp0) / timed,
        "mb_per_token": (eng.stats.bytes_uploaded - bytes0) / 2**20 / timed,
    }


def run(steps: int = 16, spec_ks: Sequence[int] = (2, 4, 8),
        quants: Sequence[str] = ("int8", "int4")) -> Dict:
    from repro.config import get_config
    from repro.configs import reduce_for_smoke
    from repro.models import init_params

    # f32 so the host miss correction is bit-exact against device compute
    cfg = dataclasses.replace(
        reduce_for_smoke(get_config("qwen2-moe-a2.7b")), dtype="float32"
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (2, 12)).astype(np.int32)

    rows = {}
    e = cfg.moe.num_experts
    spec_paths = tuple(f"spec{k}" for k in spec_ks)
    for suffix, mode, slots in (
        ("rotary", "rotary", 6),       # slot-starved: misses common, replay paid
        ("rotary_hi", "rotary", e),    # paper regime: prefetch covers routing
        ("full", "full", 0),
    ):
        for path in PATHS + spec_paths:
            rows[f"{path}_{suffix}"] = _run_engine(
                cfg, params, mode, slots, path, prompt, steps
            )

    # (a) greedy tokens identical across all paths, every residency mode —
    # including every spec-K window size (rollback + replay keep exactness)
    for suffix in ("rotary", "rotary_hi", "full"):
        for path in ("layer", "fused") + spec_paths:
            np.testing.assert_array_equal(
                rows[f"seed_{suffix}"]["tokens"], rows[f"{path}_{suffix}"]["tokens"]
            )
    # (b) accounting mechanism unchanged: all routed assignments counted and
    # every miss host-corrected, in every path
    for path in PATHS + spec_paths:
        s = rows[f"{path}_rotary"]["engine"].stats
        assert s.hits + s.misses > 0
        assert sum(l.host_computed for l in s.layers.values()) == s.misses, path
        assert (s.hits + s.misses
                == rows["seed_rotary"]["engine"].stats.hits
                + rows["seed_rotary"]["engine"].stats.misses)
    # slot-starved fused decode actually exercised the replay machinery
    assert rows["fused_rotary"]["engine"].stats.replayed_steps > 0
    # (c) miss-free fused decode: ONE queue-draining pull and ONE compiled
    # program launch per token; the per-layer hot path stays O(layers)
    for suffix in ("full", "rotary_hi"):
        r = rows[f"fused_{suffix}"]
        assert r["sync_pulls_per_step"] == 1.0, r
        assert r["dispatches_per_step"] == 1.0, r
        assert r["engine"].stats.misses == 0
        assert rows[f"layer_{suffix}"]["dispatches_per_step"] >= 2 * cfg.num_layers
    # (c') miss-free spec-K decode: 1/K pulls per token, and on full residency
    # (no snapshot needed — misses impossible) 1/K launches per token
    for k in spec_ks:
        for suffix in ("full", "rotary_hi"):
            r = rows[f"spec{k}_{suffix}"]
            s = r["engine"].stats
            assert s.misses == 0
            assert r["sync_pulls_per_step"] == 1.0 / k, (k, suffix, r)
            # (e) greedy self-draft with identical weights must accept every
            # drafted token when miss-free — a KV-rollback bug canary
            assert s.drafted_tokens > 0
            assert s.accepted_tokens == s.drafted_tokens
            assert s.accept_rate >= 1.0
        assert rows[f"spec{k}_full"]["dispatches_per_step"] == 1.0 / k
        # slot-starved spec windows actually rolled back and replayed
        assert rows[f"spec{k}_rotary"]["engine"].stats.replayed_steps > 0

    # ---- quantized row family: link traffic + within-format exactness -----
    for quant in quants:
        for suffix, mode, slots in (
            ("rotary", "rotary", 6),
            ("rotary_hi", "rotary", e),
            ("full", "full", 0),
        ):
            rows[f"fused_{suffix}@{quant}"] = _run_engine(
                cfg, params, mode, slots, "fused", prompt, steps, quant=quant
            )
        rows[f"spec4_rotary_hi@{quant}"] = _run_engine(
            cfg, params, "rotary", e, "spec4", prompt, steps, quant=quant
        )
        rows[f"spec4_rotary@{quant}"] = _run_engine(
            cfg, params, "rotary", 6, "spec4", prompt, steps, quant=quant
        )
        # (f) quantized decode is exactness-clean WITHIN its format: full
        # residency, slot-starved rotary (host-corrected misses), prefetch-
        # covered rotary and rotary+spec-4 agree token-for-token
        base = rows[f"fused_full@{quant}"]["tokens"]
        for label in (f"fused_rotary@{quant}", f"fused_rotary_hi@{quant}",
                      f"spec4_rotary_hi@{quant}", f"spec4_rotary@{quant}"):
            np.testing.assert_array_equal(base, rows[label]["tokens"], err_msg=label)
        # the slot-starved quant row actually exercised quantized replay
        assert rows[f"fused_rotary@{quant}"]["engine"].stats.misses > 0
    if "int4" in quants:
        # (f) the int4 store ships <= 0.30x the f16 bytes per rotated expert
        # (packed nibbles + f16 group scale/min planes vs 2 bytes/element)
        from repro.core.slots import quantized_expert_bytes

        eng4 = rows["fused_rotary@int4"]["engine"]
        store = eng4.manager.stores[0]
        f16_bytes = quantized_expert_bytes(
            {n: w.shape[1:] for n, w in eng4.host_experts[0].items()},
            None, dtype_bytes=2,
        )
        ratio = store.bytes_per_expert / f16_bytes
        assert ratio <= 0.30, f"int4 bytes/expert {ratio:.3f}x f16 exceeds 0.30x"
        rows["int4_bytes_ratio_vs_f16"] = ratio

    # ---- asynchronous prefetch row family: double-buffered slot planes ----
    pf_defs = [
        ("fused_full_pf", "fused_full", "full", 0, "fused", None),
        ("fused_rotary_hi_pf", "fused_rotary_hi", "rotary", e, "fused", None),
        ("fused_rotary_pf", "fused_rotary", "rotary", 6, "fused", None),
    ]
    if 4 in spec_ks:
        pf_defs.append(
            ("spec4_rotary_pf", "spec4_rotary", "rotary", 6, "spec4", None))
    if "int4" in quants:
        pf_defs.append(("fused_rotary_pf@int4", "fused_rotary@int4",
                        "rotary", 6, "fused", "int4"))
    for label, twin, mode, slots, path, quant in pf_defs:
        rows[label] = _run_engine(cfg, params, mode, slots, path, prompt,
                                  steps, quant=quant, prefetch=True)
        # (g) the shadow-generation flip, the mispredict correction pass and
        # the miss relaunch are invisible in the output: greedy tokens
        # bit-identical to the synchronous-rotation twin row
        np.testing.assert_array_equal(
            rows[twin]["tokens"], rows[label]["tokens"], err_msg=label)
    # prefetch must not introduce misses where rotation already covered
    for label in ("fused_full_pf", "fused_rotary_hi_pf"):
        assert rows[label]["engine"].stats.misses == 0, label
    # the slot-starved row actually exercised the machinery: shadow uploads
    # launched during window compute, and missed steps resolved by uploading
    # the missed experts and re-launching the ONE compiled step (the suffix
    # replay remains only as the fallback for infeasible windows)
    spf = rows["fused_rotary_pf"]["engine"].stats
    assert spf.prefetch_launched > 0
    assert spf.overlap_ms > 0
    assert spf.relaunched_steps > 0

    # ---- sampled (temperature > 0) row family: the *_t rows ---------------
    # single-token sampled vs spec-4 sampled on the prefetch-covered regime:
    # both draft on-device from the warped distribution with position-keyed
    # draws, so the streams are bitwise-identical and the window's win is
    # pure launch/pull amortization (plus skipping the per-token host draw)
    from repro.serving.sampler import SamplerConfig

    smp = SamplerConfig(temperature=0.8, top_k=20, top_p=0.95, seed=11)
    for label, path in (("fused_rotary_hi_t", "fused"),
                        ("spec4_rotary_hi_t", "spec4")):
        rows[label] = _run_engine(
            cfg, params, "rotary", e, path, prompt, steps, sampler=smp
        )
    # (h) sampled spec-4 == sampled single-token bitwise (same seeded draws,
    # stochastic acceptance over identical draft/verify distributions), and
    # miss-free self-drafting still accepts everything (ratio exactly 1.0)
    np.testing.assert_array_equal(
        rows["fused_rotary_hi_t"]["tokens"], rows["spec4_rotary_hi_t"]["tokens"],
        err_msg="sampled spec-4 stream diverged from sampled single-token",
    )
    st4 = rows["spec4_rotary_hi_t"]["engine"].stats
    assert st4.misses == 0 and st4.drafted_tokens > 0
    assert st4.accept_rate >= 1.0, st4.summary()

    # the >=1.5x prefetch gate divides two rows the per-row harness timed
    # minutes apart; re-time the pair INTERLEAVED (round-robin, like the
    # prefill family's rounds) so host-load drift cannot land on one side
    # of the ratio — 4 rounds is what the 128-entry KV cache leaves room for
    import gc

    gc.collect()      # the row sweep above left garbage; not in a timed round
    pair = ("fused_rotary", "fused_rotary_pf")
    walls = {label: [] for label in pair}
    outs: Dict = {label: [] for label in pair}
    for _ in range(4):
        for label in pair:
            eng = rows[label]["engine"]
            t0 = time.perf_counter()
            outs[label].append(eng.decode(eng.last_logits, steps))
            walls[label].append(time.perf_counter() - t0)
    np.testing.assert_array_equal(       # the re-time rounds stay exact too
        np.concatenate(outs[pair[0]], axis=1),
        np.concatenate(outs[pair[1]], axis=1),
        err_msg="prefetch diverged from sync rotation in the re-time rounds",
    )
    for label in pair:
        rows[label]["s_per_step"] = min(walls[label]) / steps

    # the sampled >=1.4x gate gets the same interleaved treatment; both
    # engines sit at the same cur_len, so the re-time rounds must stay
    # bitwise-identical too (same position-keyed draws on both sides)
    gc.collect()
    pair_t = ("fused_rotary_hi_t", "spec4_rotary_hi_t")
    walls_t = {label: [] for label in pair_t}
    outs_t: Dict = {label: [] for label in pair_t}
    for _ in range(4):
        for label in pair_t:
            eng = rows[label]["engine"]
            t0 = time.perf_counter()
            outs_t[label].append(eng.decode(eng.last_logits, steps, sampler=smp))
            walls_t[label].append(time.perf_counter() - t0)
    np.testing.assert_array_equal(
        np.concatenate(outs_t[pair_t[0]], axis=1),
        np.concatenate(outs_t[pair_t[1]], axis=1),
        err_msg="sampled spec-4 diverged from single-token in re-time rounds",
    )
    for label in pair_t:
        rows[label]["s_per_step"] = min(walls_t[label]) / steps
    return rows


def run_prefill(prompt_len: int = 256, chunk: int = 32,
                decode_check: int = 4) -> Dict:
    """Prefill / time-to-first-token row family: chunked fused prefill vs the
    layer-walk paths on a long prompt.

    Rows (reduced ``qwen2_moe_a2_7b`` at 6 MoE layers — prefill's win is
    amortizing the per-layer host syncs, which scale with depth):

    * ``prefill_legacy`` — full-sequence layer walk (today's default,
      ``prefill_chunk=None``): one jitted attn+MoE pair per layer with a host
      sync per MoE layer, every distinct prompt length retraces and
      recompiles the whole stack;
    * ``prefill_walk``   — chunked layer walk (``fused_decode=False``): the
      same per-layer launches per chunk, rotation at chunk boundaries — the
      apples-to-apples baseline for the fused path;
    * ``prefill_fused``  — ONE compiled whole-stack launch + one
      queue-draining pull + one coalesced rotation window per chunk;
    * ``prefill_walk@int4`` / ``prefill_fused@int4`` — the chunked paths on
      grouped-int4 slots (within-format exactness pair).

    TTFT here = prefill wall time (the first token is a host argmax of the
    returned logits); ``ttft_new_len_s`` re-prefills at an UNSEEN prompt
    length — the serving-realistic admission case, where the legacy path
    pays a full whole-stack retrace + recompile and the chunked paths reuse
    their power-of-two chunk programs. Acceptance: fused beats the chunked
    layer walk >= 1.3x steady-state (prompts >= 256) and the legacy walk
    >= 2x on a new length; logits bit-identical between the chunked paths
    within each slot format; greedy continuations identical across paths;
    and the fused dispatch bound holds: exactly one whole-stack launch and
    one queue-draining pull per chunk, zero replays in the prefetch-covered
    regime.
    """
    import dataclasses as _dc

    from repro.config import ResidencyConfig, get_config
    from repro.configs import reduce_for_smoke
    from repro.core import RotaryEngine
    from repro.core.engine import prefill_chunk_plan
    from repro.models import init_params
    from repro.models.transformer import Runtime

    cfg = _dc.replace(
        reduce_for_smoke(get_config("qwen2-moe-a2.7b"), max_repeats=6),
        dtype="float32",
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (1, prompt_len)).astype(np.int32)
    new_len = prompt_len + 2 * chunk       # unseen length, existing chunk shapes
    prompt_new = rng.integers(0, cfg.vocab_size, (1, new_len)).astype(np.int32)
    rt_len = max(128, new_len + decode_check + 8)
    e = cfg.moe.num_experts

    def mk(path: str, quant: str | None = None) -> RotaryEngine:
        return RotaryEngine(
            cfg, params,
            ResidencyConfig(mode="rotary", num_slots=e, quantization=quant),
            rt=Runtime(cache_len=rt_len), batch=1,
            fused_decode=False if path == "walk" else None,
            prefill_chunk=None if path == "legacy" else chunk,
        )

    labels = (
        ("prefill_legacy", "legacy", None),
        ("prefill_walk", "walk", None),
        ("prefill_fused", "fused", None),
        ("prefill_walk@int4", "walk", "int4"),
        ("prefill_fused@int4", "fused", "int4"),
    )
    reps = 5
    engines, snaps = {}, {}
    for label, path, quant in labels:
        eng = mk(path, quant)
        eng.prefill(prompt)                       # warmup: populate jit caches
        engines[label] = eng
        snaps[label] = (eng.stats.sync_pulls, eng.stats.prefill_chunks)
    # timing rounds are INTERLEAVED across rows (round-robin, best-of-N per
    # row): the speedup gates below are ratios, and timing the rows
    # back-to-back would let slow host-load drift land entirely on one row
    import gc

    gc.collect()      # don't let warmup garbage collect inside a timed round
    walls: Dict = {label: [] for label, _, _ in labels}
    logits: Dict = {}
    for _ in range(reps):
        for label, _, _ in labels:
            t0 = time.perf_counter()
            logits[label] = engines[label].prefill(prompt)
            walls[label].append(time.perf_counter() - t0)
    rows: Dict = {}
    for label, path, quant in labels:
        eng = engines[label]
        pulls0, chunks0 = snaps[label]
        tokens = eng.decode(logits[label], decode_check)
        chunks = (eng.stats.prefill_chunks - chunks0) // reps
        pulls = (eng.stats.sync_pulls - pulls0 - decode_check) / reps
        # admission at an unseen prompt length: chunked paths reuse their
        # power-of-two chunk programs, the legacy path recompiles the stack
        t0 = time.perf_counter()
        eng.prefill(prompt_new)
        ttft_new = time.perf_counter() - t0
        rows[label] = {
            "engine": eng,
            "logits": logits[label],
            "tokens": tokens,
            "ttft_s": min(walls[label]),
            "ttft_new_len_s": ttft_new,
            "chunks": chunks,
            "pulls_per_prefill": pulls,
        }

    n_chunks = len(prefill_chunk_plan(prompt_len, chunk))
    fused = rows["prefill_fused"]
    # (a) chunked paths bit-identical (logits) WITHIN each slot format;
    # greedy continuation identical across the f32 paths including the
    # legacy full-sequence walk (quantized rows are exactness-clean within
    # their format, not against the f32 store)
    np.testing.assert_array_equal(
        rows["prefill_walk"]["logits"], fused["logits"],
        err_msg="fused chunked prefill logits != chunked layer-walk logits",
    )
    np.testing.assert_array_equal(
        rows["prefill_walk@int4"]["logits"], rows["prefill_fused@int4"]["logits"],
        err_msg="int4 fused chunked prefill logits != int4 layer-walk logits",
    )
    for label in ("prefill_legacy", "prefill_walk"):
        np.testing.assert_array_equal(
            rows[label]["tokens"], fused["tokens"], err_msg=label
        )
    np.testing.assert_array_equal(
        rows["prefill_walk@int4"]["tokens"], rows["prefill_fused@int4"]["tokens"],
        err_msg="int4 chunked prefill decode tokens diverge across paths",
    )
    # (b) dispatch bound: ONE whole-stack launch and ONE queue-draining pull
    # per chunk, no replays in the prefetch-covered regime
    assert fused["chunks"] == n_chunks, (fused["chunks"], n_chunks)
    assert fused["pulls_per_prefill"] == n_chunks, fused["pulls_per_prefill"]
    assert fused["engine"].stats.prefill_replays == 0
    assert fused["engine"].stats.misses == 0
    # (c) the acceptance gates: fused >= 1.3x the chunked layer walk steady-
    # state, and >= 2x the legacy walk at an unseen prompt length (bounded
    # compile cache: the legacy path retraces the whole stack per length)
    speedup_walk = rows["prefill_walk"]["ttft_s"] / fused["ttft_s"]
    speedup_legacy = rows["prefill_legacy"]["ttft_s"] / fused["ttft_s"]
    speedup_new_len = (
        rows["prefill_legacy"]["ttft_new_len_s"] / fused["ttft_new_len_s"]
    )
    assert speedup_walk >= 1.3, (
        f"fused chunked prefill only {speedup_walk:.2f}x the layer walk"
    )
    assert speedup_new_len >= 2.0, (
        f"fused chunked prefill only {speedup_new_len:.2f}x the legacy walk "
        f"at an unseen prompt length"
    )
    rows["speedups"] = {
        "prefill_fused_vs_walk": speedup_walk,
        "prefill_fused_vs_legacy": speedup_legacy,
        "prefill_fused_vs_legacy_new_len": speedup_new_len,
    }
    rows["prompt_len"] = prompt_len
    rows["chunk"] = chunk
    return rows


def run_trace_overhead(steps: int = 16, rounds: int = 6) -> Dict:
    """Tracing-overhead row pair: the miss-starved fused+prefetch rotary
    workload with the event tracer ON vs OFF.

    Three engines over identical work: untraced (``trace=None``), traced
    (live :class:`repro.obs.Tracer`), and disabled (``Tracer(enabled=False)``).
    The disabled engine's overhead is asserted STRUCTURALLY, not by timing:
    the engine normalises a disabled tracer to no tracer reference at all
    (``eng._tr is None``), so its hot path executes exactly the instructions
    of the untraced one — unmeasurable by construction. The traced/untraced
    pair is timed interleaved (round-robin best-of-N, like the prefetch
    gate) and gated at <= 3% slowdown; the captured trace must pass the
    contract auditor.
    """
    import dataclasses as _dc
    import gc

    from repro.config import ResidencyConfig, get_config
    from repro.configs import reduce_for_smoke
    from repro.core import RotaryEngine
    from repro.models import init_params
    from repro.models.transformer import Runtime
    from repro.obs import Tracer, audit

    cfg = _dc.replace(
        reduce_for_smoke(get_config("qwen2-moe-a2.7b")), dtype="float32"
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = (np.random.default_rng(0)
              .integers(0, cfg.vocab_size, (2, 12)).astype(np.int32))

    def mk(trace):
        eng = RotaryEngine(
            cfg, params, ResidencyConfig(mode="rotary", num_slots=6),
            rt=Runtime(cache_len=max(128, prompt.shape[1] + steps + 8)),
            batch=2, prefetch=True, trace=trace,
        )
        logits = eng.prefill(prompt)
        eng.decode(logits, 2)                  # warmup: jit caches populated
        return eng

    tracer = Tracer()
    engines = {
        "untraced": mk(None),
        "traced": mk(tracer),
        "disabled": mk(Tracer(enabled=False)),
    }
    # the structural zero-overhead-when-off contract
    assert engines["disabled"]._tr is None
    assert engines["untraced"]._tr is None
    assert engines["traced"]._tr is tracer

    gc.collect()
    walls: Dict = {label: [] for label in engines}
    outs: Dict = {label: [] for label in engines}
    for _ in range(rounds):
        for label, eng in engines.items():
            t0 = time.perf_counter()
            outs[label].append(eng.decode(eng.last_logits, steps))
            walls[label].append(time.perf_counter() - t0)
    # identical work: greedy tokens bit-identical across all three engines
    base = np.concatenate(outs["untraced"], axis=1)
    for label in ("traced", "disabled"):
        np.testing.assert_array_equal(
            base, np.concatenate(outs[label], axis=1), err_msg=label)
    ratio = min(walls["traced"]) / min(walls["untraced"])
    # the captured trace passes the contract auditor, and its span-derived
    # prefetch overlap agrees with the legacy wall-clock accounting
    report = audit(tracer)
    report.raise_for_violations()
    stats_overlap = engines["traced"].stats.overlap_ms
    span_overlap = report.overlap_ms
    assert abs(span_overlap - stats_overlap) <= max(1.0, 0.01 * stats_overlap), (
        span_overlap, stats_overlap)
    return {
        "ms_per_step_untraced": min(walls["untraced"]) / steps * 1e3,
        "ms_per_step_traced": min(walls["traced"]) / steps * 1e3,
        "traced_over_untraced": ratio,
        "disabled_is_noop": True,
        "events": len(tracer),
        "audit": report.summary(),
    }


def main(argv: Sequence[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec-k", default="2,4,8",
                    help="comma-separated speculative window sizes to row out")
    ap.add_argument("--quantization", default="int8,int4",
                    help="comma-separated slot formats for the quantized row "
                         "family (subset of int8,int4; empty disables)")
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--prefill-len", type=int, default=256,
                    help="prompt length for the prefill/TTFT row family "
                         "(0 disables the family)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="chunk length for the chunked-prefill rows "
                         "(power of two)")
    args = ap.parse_args(argv)
    spec_ks: Tuple[int, ...] = tuple(
        int(t) for t in args.spec_k.split(",") if t.strip()
    )
    assert 4 in spec_ks, "the >=1.2x acceptance gate is pinned at K=4"
    quants: Tuple[str, ...] = tuple(
        t for t in args.quantization.split(",") if t.strip() and t != "none"
    )
    assert all(q in ("int8", "int4") for q in quants), quants
    steps = args.steps
    rows = run(steps, spec_ks, quants)
    spec_paths = tuple(f"spec{k}" for k in spec_ks)
    order = [f"{p}_{s}" for s in ("full", "rotary_hi", "rotary")
             for p in PATHS + spec_paths]
    order += [f"fused_{s}@{q}" for q in quants
              for s in ("full", "rotary_hi", "rotary")]
    order += [f"spec4_{s}@{q}" for q in quants for s in ("rotary_hi", "rotary")]
    order += ["fused_full_pf", "fused_rotary_hi_pf", "fused_rotary_pf"]
    if 4 in spec_ks:
        order.append("spec4_rotary_pf")
    if "int4" in quants:
        order.append("fused_rotary_pf@int4")
    order += ["fused_rotary_hi_t", "spec4_rotary_hi_t"]
    for label in order:
        r = rows[label]
        print(f"  {label:22s} {r['s_per_step']*1e3:8.2f} ms/step  "
              f"sync_pulls/step={r['sync_pulls_per_step']:.1f}  "
              f"dispatches/step={r['dispatches_per_step']:.1f}  "
              f"MB/token={r['mb_per_token']:.3f}")
    speedups = {}
    for suffix in ("full", "rotary_hi"):
        layer = rows[f"layer_{suffix}"]["s_per_step"]
        fused = rows[f"fused_{suffix}"]["s_per_step"]
        seed = rows[f"seed_{suffix}"]["s_per_step"]
        speedups[suffix] = {
            "fused_vs_layer": layer / fused,
            "fused_vs_seed": seed / fused,
        }
        for k in spec_ks:
            spec = rows[f"spec{k}_{suffix}"]["s_per_step"]
            speedups[suffix][f"spec{k}_vs_fused"] = fused / spec
        print(f"  miss-free {suffix}: fused vs per-layer {layer / fused:.2f}x, "
              f"fused vs seed {seed / fused:.2f}x, "
              + ", ".join(
                  f"spec{k} vs fused {speedups[suffix][f'spec{k}_vs_fused']:.2f}x"
                  for k in spec_ks
              ))
    print("  (slot-starved rotary pays whole-suffix replay per missed step — "
          "spec windows additionally roll back and re-draft the rejected "
          "suffix; the prefetch-covered regime is the paper's operating point)")
    spf = rows["fused_rotary_pf"]["engine"].stats
    pf_speedup = (rows["fused_rotary"]["s_per_step"]
                  / rows["fused_rotary_pf"]["s_per_step"])
    print(f"  miss-starved rotary: prefetch vs sync rotation {pf_speedup:.2f}x  "
          f"(overlap {spf.overlap_ms:.1f} ms, "
          f"launched {spf.prefetch_launched}, hits {spf.prefetch_hits}, "
          f"relaunched {spf.relaunched_steps}, replayed {spf.replayed_steps})")
    for suffix, sp in speedups.items():
        print(f"decode_hot_path,speedup_fused_vs_layer_{suffix},{sp['fused_vs_layer']:.3f}")
        print(f"decode_hot_path,speedup_fused_vs_seed_{suffix},{sp['fused_vs_seed']:.3f}")
        for k in spec_ks:
            print(f"decode_hot_path,speedup_spec{k}_vs_fused_{suffix},"
                  f"{sp[f'spec{k}_vs_fused']:.3f}")
    print(f"decode_hot_path,speedup_prefetch_fused_rotary,{pf_speedup:.3f}")
    print(f"decode_hot_path,ms_per_step_fused_rotary_pf,"
          f"{rows['fused_rotary_pf']['s_per_step']*1e3:.3f}")
    print(f"decode_hot_path,overlap_ms_fused_rotary_pf,{spf.overlap_ms:.3f}")
    print("decode_hot_path,prefetch_tokens_identical,1")
    print(f"decode_hot_path,ms_per_step_fused_full,{rows['fused_full']['s_per_step']*1e3:.3f}")
    print(f"decode_hot_path,accept_rate_spec4_full,"
          f"{rows['spec4_full']['engine'].stats.accept_rate:.3f}")
    print("decode_hot_path,tokens_identical,1")
    # sampled *_t rows: spec-4 sampled vs single-token sampled, same stream
    sampled_speedup = (rows["fused_rotary_hi_t"]["s_per_step"]
                       / rows["spec4_rotary_hi_t"]["s_per_step"])
    print(f"  sampled (t=0.8) rotary_hi: spec4 vs single-token "
          f"{sampled_speedup:.2f}x  "
          f"(accept_rate {rows['spec4_rotary_hi_t']['engine'].stats.accept_rate:.3f}, "
          f"tokens bitwise-identical)")
    print(f"decode_hot_path,speedup_spec4_vs_fused_sampled_rotary_hi,"
          f"{sampled_speedup:.3f}")
    for label in ("fused_rotary_hi_t", "spec4_rotary_hi_t"):
        print(f"decode_hot_path,accept_rate_{label},"
              f"{rows[label]['engine'].stats.accept_rate:.3f}")
    print("decode_hot_path,sampled_tokens_identical,1")
    if quants:
        # link traffic: the slot-starved rotary workload (the regime that
        # actually rotates every window) priced in each slot format, MB per
        # decoded token — the f16-vs-int8-vs-int4 shrink in one column
        for q in quants:
            print(f"decode_hot_path,mb_per_token_fused_rotary_{q},"
                  f"{rows[f'fused_rotary@{q}']['mb_per_token']:.4f}")
        print(f"decode_hot_path,mb_per_token_fused_rotary_f32,"
              f"{rows['fused_rotary']['mb_per_token']:.4f}")
    if "int4" in quants:
        print(f"decode_hot_path,int4_bytes_ratio_vs_f16,"
              f"{rows['int4_bytes_ratio_vs_f16']:.4f}")
        print("decode_hot_path,int4_tokens_identical,1")
    # ---- prefill / time-to-first-token row family -------------------------
    prefill_rows = None
    if args.prefill_len:
        prefill_rows = run_prefill(args.prefill_len, args.prefill_chunk)
        for label in ("prefill_legacy", "prefill_walk", "prefill_fused",
                      "prefill_walk@int4", "prefill_fused@int4"):
            r = prefill_rows[label]
            print(f"  {label:22s} TTFT {r['ttft_s']*1e3:8.2f} ms  "
                  f"new-len {r['ttft_new_len_s']*1e3:8.2f} ms  "
                  f"chunks={r['chunks']}  pulls/prefill={r['pulls_per_prefill']:.1f}")
        for name, v in prefill_rows["speedups"].items():
            print(f"decode_hot_path,speedup_{name},{v:.3f}")
        print("decode_hot_path,prefill_tokens_identical,1")

    # ---- tracing-overhead row pair ----------------------------------------
    trace_rows = run_trace_overhead(steps)
    print(f"  tracing overhead (fused+prefetch rotary): "
          f"untraced {trace_rows['ms_per_step_untraced']:.2f} ms/step, "
          f"traced {trace_rows['ms_per_step_traced']:.2f} ms/step "
          f"({(trace_rows['traced_over_untraced'] - 1) * 100:+.1f}%), "
          f"{trace_rows['events']} events, "
          f"audit ok={trace_rows['audit']['ok']}")
    print(f"decode_hot_path,trace_overhead_ratio,"
          f"{trace_rows['traced_over_untraced']:.4f}")
    print("decode_hot_path,trace_audit_ok,1")

    payload = {
        "config": "qwen2_moe_a2_7b_reduced_f32",
        "steps_timed": steps,
        "rows": {
            label: {
                "ms_per_step": rows[label]["s_per_step"] * 1e3,
                "sync_pulls_per_step": rows[label]["sync_pulls_per_step"],
                "dispatches_per_step": rows[label]["dispatches_per_step"],
                "mb_per_token": rows[label]["mb_per_token"],
                "misses": int(rows[label]["engine"].stats.misses),
                "replayed_steps": int(rows[label]["engine"].stats.replayed_steps),
                "drafted_tokens": int(rows[label]["engine"].stats.drafted_tokens),
                "accepted_tokens": int(rows[label]["engine"].stats.accepted_tokens),
                "accept_rate": rows[label]["engine"].stats.accept_rate,
                "prefetch_launched": int(
                    rows[label]["engine"].stats.prefetch_launched),
                "prefetch_hits": int(rows[label]["engine"].stats.prefetch_hits),
                "prefetch_wasted_bytes": int(
                    rows[label]["engine"].stats.prefetch_wasted_bytes),
                "overlap_ms": rows[label]["engine"].stats.overlap_ms,
                "relaunched_steps": int(
                    rows[label]["engine"].stats.relaunched_steps),
            }
            for label in order
        },
        "speedups": speedups,
        "tokens_identical": True,
        "prefetch": {
            "speedup_fused_rotary": pf_speedup,
            "overlap_ms_fused_rotary_pf": spf.overlap_ms,
            "prefetch_launched": int(spf.prefetch_launched),
            "prefetch_hits": int(spf.prefetch_hits),
            "prefetch_wasted_bytes": int(spf.prefetch_wasted_bytes),
            "relaunched_steps": int(spf.relaunched_steps),
            "tokens_identical": True,
        },
    }
    payload["trace"] = trace_rows
    payload["sampled"] = {
        "speedup_spec4_vs_fused_rotary_hi": sampled_speedup,
        "accept_rate_spec4_rotary_hi_t":
            rows["spec4_rotary_hi_t"]["engine"].stats.accept_rate,
        "tokens_identical": True,
    }
    if "int4" in quants:
        payload["int4_bytes_ratio_vs_f16"] = rows["int4_bytes_ratio_vs_f16"]
        payload["int4_tokens_identical"] = True
    if prefill_rows is not None:
        payload["prefill"] = {
            "prompt_len": prefill_rows["prompt_len"],
            "chunk": prefill_rows["chunk"],
            "rows": {
                label: {
                    "ttft_ms": prefill_rows[label]["ttft_s"] * 1e3,
                    "ttft_new_len_ms": prefill_rows[label]["ttft_new_len_s"] * 1e3,
                    "chunks": prefill_rows[label]["chunks"],
                    "pulls_per_prefill": prefill_rows[label]["pulls_per_prefill"],
                    "prefill_replays": int(
                        prefill_rows[label]["engine"].stats.prefill_replays
                    ),
                    "misses": int(prefill_rows[label]["engine"].stats.misses),
                }
                for label in ("prefill_legacy", "prefill_walk", "prefill_fused",
                              "prefill_walk@int4", "prefill_fused@int4")
            },
            "speedups": prefill_rows["speedups"],
            "tokens_identical": True,
        }
    # machine-readable tier-1 pass-count trajectory (tools/tier1_delta.py):
    # embedded whenever a `make tier1` log exists next to this benchmark.
    # Loaded by explicit file path — tools/ is not a package, and mutating
    # sys.path would shadow any other module named tier1_delta process-wide
    import importlib.util
    import os

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "repro_tools_tier1_delta",
        os.path.join(repo_root, "tools", "tier1_delta.py"),
    )
    tier1_delta = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tier1_delta)
    tier1 = tier1_delta.payload_from_files(
        os.path.join(repo_root, ".tier1.log"),
        os.path.join(repo_root, "CHANGES.md"),
    )
    if tier1 is not None:
        payload["tier1"] = tier1
        print(f"decode_hot_path,tier1_passed,{tier1['passed']}")
    with open("BENCH_decode.json", "w") as f:
        json.dump(payload, f, indent=2)
    print("  wrote BENCH_decode.json")
    # acceptance: the fused step must beat the PR-1 per-layer hot path by
    # >= 1.3x on the miss-free steady state (best of the two covered regimes;
    # the other must at least not regress past timing noise)
    best = max(sp["fused_vs_layer"] for sp in speedups.values())
    worst = min(sp["fused_vs_layer"] for sp in speedups.values())
    assert best >= 1.3, speedups
    assert worst >= 1.05, speedups
    # acceptance: speculative windows at K=4 must beat the fused single-token
    # path >= 1.2x miss-free (amortized launches + pulls + rotation), and not
    # regress past timing noise in the other covered regime
    best4 = max(sp["spec4_vs_fused"] for sp in speedups.values())
    worst4 = min(sp["spec4_vs_fused"] for sp in speedups.values())
    assert best4 >= 1.2, speedups
    assert worst4 >= 1.0, speedups
    # acceptance: sampled spec-4 must beat the sampled single-token fused
    # path >= 1.4x miss-free — the window amortizes the launch+pull AND the
    # per-token host draw sync, so its bar is higher than the greedy 1.2x
    assert sampled_speedup >= 1.4, (
        f"sampled spec4 only {sampled_speedup:.2f}x single-token sampled"
    )
    # acceptance: on the miss-starved fused rotary row, asynchronous prefetch
    # (shadow-generation uploads + compiled-step miss relaunch) must beat the
    # synchronous-rotation baseline >= 1.5x, with real overlap on record —
    # the prefetch engine cannot win by merely skipping work
    assert pf_speedup >= 1.5, (pf_speedup, spf.summary())
    assert spf.overlap_ms > 0, spf.summary()
    # acceptance: live tracing costs <= 3% on the miss-starved fused+prefetch
    # hot path (ring-buffer appends only), and a DISABLED tracer is a no-op
    # by construction (asserted structurally inside run_trace_overhead)
    assert trace_rows["traced_over_untraced"] <= 1.03, trace_rows
    assert trace_rows["disabled_is_noop"]


if __name__ == "__main__":
    main()
