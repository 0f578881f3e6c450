"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Two engines (DESIGN.md §2):
  * ``--engine rotary``  — the paper-faithful per-layer engine
    (repro.core.engine.RotaryEngine): host-resident experts, rotating slots,
    hidden-state-guided prefetch, host-GEMM miss correction. MoE archs only.
  * ``--engine batch``   — compiled continuous-batching engine
    (repro.serving.ServingEngine), any arch; optional rotary residency
    rotating between steps. KV lives in a paged pool on KV-cache-only
    stacks (``--kv-pages`` / ``--kv-page-size``); ``--arrival-rate`` replays
    a seeded Poisson arrival trace against the live engine (request-level
    joins between window launches) instead of submitting everything up
    front.

Sizes: ``--size smoke`` (the default) shrinks the config to CPU-test widths
(``reduce_for_smoke``); ``--size full`` keeps the published widths, expert
count, top-k, vocab and dtype, and ``--layers N`` cuts only the depth. An
engine with a residency manager gets its experts built one layer at a time
straight into host memory, so the whole expert set is never on the device.
``build_engine`` is the construction both this CLI and ``chip_smoke.py``
call.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

# CLI spelling -> ResidencyConfig.quantization ("none" is how the default is
# spelled on the command line; None itself is impossible to type)
QUANT_CHOICES = {"none": None, "int8": "int8", "int4": "int4"}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--size", default="smoke", choices=["smoke", "full"],
                    help="smoke = CPU-test widths; full = the published "
                         "widths, experts, top-k, vocab and dtype")
    ap.add_argument("--layers", type=int, default=0,
                    help="--size full: keep only the first N layers "
                         "(0 = the published depth)")
    ap.add_argument("--engine", default="batch", choices=["batch", "rotary"])
    ap.add_argument("--residency", default="full",
                    choices=["full", "rotary", "lru", "static"])
    ap.add_argument("--slots", type=int, default=0, help="residency slots per layer")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--batch", type=int, default=1,
                    help="rotary-engine decode batch (requests served per group)")
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--host-routing", action="store_true",
                    help="seed-style per-layer host routing (benchmark baseline)")
    ap.add_argument("--spec-k", type=int, default=1,
                    help="rotary-engine speculative window (tokens per fused "
                         "launch; 1 = single-token decode)")
    ap.add_argument("--spec-cap", type=int, default=4,
                    help="batch-engine per-row speculative length cap "
                         "(1 disables speculation)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="rotary-engine chunked prefill: power-of-two chunk "
                         "length (0 = legacy full-sequence layer walk). Long "
                         "prompts ingest at one compiled launch + one "
                         "coalesced rotation window per chunk")
    ap.add_argument("--quantization", default="none",
                    choices=sorted(QUANT_CHOICES),
                    help="slot-store weight format (int4 = grouped "
                         "two-nibbles-per-byte, ~4x smaller rotations)")
    ap.add_argument("--quant-group", type=int, default=64,
                    help="int4 rows per scale/min group (Q4_K_M-style)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="batch-engine Poisson arrival rate (requests/s): "
                         "submit on a seeded arrival trace and tick the "
                         "engine live — requests join/leave the window as "
                         "they arrive/finish (0 = submit everything up "
                         "front)")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="batch-engine KV pool size in pages (0 = auto: "
                         "batch-slots full rows)")
    ap.add_argument("--kv-page-size", type=int, default=16,
                    help="KV pool page granularity in cache positions")
    ap.add_argument("--prefetch", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="asynchronous predictive expert prefetch: shadow-"
                         "generation uploads hidden under in-flight launches, "
                         "boundary = confirm/correct/flip (rotary engine: "
                         "plus predictive slot steering; batch engine: "
                         "overlap only). --no-prefetch (the default) keeps "
                         "the synchronous rotation path as the exactness "
                         "baseline. Loud error on unsupported combos "
                         "(host routing, LRU, non-paged batch engine)")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile the batch-engine program family before "
                         "serving (first-request latency then measures "
                         "serving, not tracing)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="capture an event-level trace of the run and write "
                         "Chrome trace-event JSON (load in Perfetto / "
                         "chrome://tracing; audit with "
                         "`python -m repro.obs.audit PATH`)")
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="serve live Prometheus metrics on "
                         "127.0.0.1:PORT/metrics while the run is in flight "
                         "(0 = off; batch engine only)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax; > 0 draws "
                         "from the warped distribution through the SAME "
                         "speculative windows, kept exact by stochastic "
                         "acceptance)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits before sampling "
                         "(0 = no top-k cut)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling: keep the smallest prefix of "
                         "probability mass >= p (1.0 = no cut)")
    ap.add_argument("--sample-seed", type=int, default=None,
                    help="PRNG seed for the sampling streams (default: "
                         "--seed). Streams are keyed per request/position, "
                         "so a fixed seed reproduces tokens bitwise across "
                         "runs regardless of batching")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def model_config(args: argparse.Namespace):
    from repro.config import get_config
    from repro.configs import cut_depth, reduce_for_smoke

    cfg = get_config(args.arch)
    if args.size == "smoke":
        return reduce_for_smoke(cfg)
    return cut_depth(cfg, args.layers) if args.layers else cfg


def _has_residency_manager(cfg, args: argparse.Namespace) -> bool:
    """Whether the engine keeps its expert warehouse in host memory behind a
    residency manager: the rotary engine always, the batch engine under a
    rotating residency."""
    return cfg.has_moe and (args.engine == "rotary" or args.residency != "full")


def init_model(cfg, args: argparse.Namespace):
    """Seeded params; experts go straight to host memory whenever the engine
    keeps its expert warehouse there."""
    from repro.models import init_params

    return init_params(cfg, jax.random.PRNGKey(args.seed),
                       experts_on_host=_has_residency_manager(cfg, args))


def build_engine(args: argparse.Namespace, cfg, params, tracer=None):
    """The engine the flags describe, over ``params`` (from ``init_model``)."""
    from repro.config import ResidencyConfig
    from repro.models.transformer import Runtime
    from repro.serving import SamplerConfig, ServingEngine

    rt = Runtime(cache_len=args.cache_len)
    slots = args.slots or (cfg.moe.num_experts * 3 // 4 if cfg.has_moe else 0)
    rescfg = None
    if _has_residency_manager(cfg, args):
        rescfg = ResidencyConfig(mode=args.residency, num_slots=slots,
                                 quantization=QUANT_CHOICES[args.quantization],
                                 quant_group_size=args.quant_group)
    if args.engine == "rotary":
        from repro.core import RotaryEngine

        assert cfg.has_moe, "--engine rotary requires an MoE arch"
        return RotaryEngine(
            cfg, params, rescfg,
            rt=rt, batch=max(1, args.batch), host_routing=args.host_routing,
            spec_k=max(1, args.spec_k),
            prefill_chunk=args.prefill_chunk or None,
            prefetch=args.prefetch,
            trace=tracer,
        )
    return ServingEngine(
        cfg, params, rt=rt, num_slots=args.batch_slots, residency=rescfg,
        sampler=SamplerConfig(
            temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
            seed=args.sample_seed if args.sample_seed is not None
            else args.seed,
        ),
        spec_cap=max(1, args.spec_cap),
        kv_page_size=args.kv_page_size,
        kv_pages=args.kv_pages or None,
        prefetch=args.prefetch,
        trace=tracer,
    )


def _measured(summary: dict) -> dict:
    """Stats as measured: the modeled clock's fields are not results."""
    return {k: v for k, v in summary.items() if not k.startswith("modeled_")}


def main(argv=None) -> None:
    from repro.launch.compile_cache import place_compile_cache
    from repro.serving import SamplerConfig

    args = build_parser().parse_args(argv)
    place_compile_cache()
    cfg = model_config(args)
    params = init_model(cfg, args)
    tracer = None
    if args.trace_out:
        from repro.obs import Tracer
        tracer = Tracer()
    rng = np.random.default_rng(args.seed)
    eng = build_engine(args, cfg, params, tracer)
    del params          # the engine holds what it needs

    if args.engine == "rotary":
        b = eng.batch
        gen_kw = {}
        if args.temperature > 0:
            gen_kw = dict(greedy=False, sampler=SamplerConfig(
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p,
                seed=args.sample_seed if args.sample_seed is not None
                else args.seed,
            ))
        # serve requests in decode groups of --batch (device-resident hot path
        # amortizes the per-step host interaction over all rows of the group)
        for g0 in range(0, args.requests, b):
            n = min(b, args.requests - g0)
            prompt = rng.integers(
                0, cfg.vocab_size, (b, args.prompt_len)
            ).astype(np.int32)
            out = eng.generate(prompt, args.max_new, **gen_kw)
            for i in range(n):
                print(f"req {g0 + i}: {out[i].tolist()}")
        print("stats:", _measured(eng.stats.summary()))
        print("per-layer residency:")
        print(eng.stats.per_layer_table())
        if tracer is not None:
            tracer.write(args.trace_out)
            print(f"trace: {len(tracer)} events -> {args.trace_out}")
        return

    metrics_server = None
    if args.metrics_port:
        from repro.obs import serve_metrics
        metrics_server = serve_metrics(eng.metrics_registry, args.metrics_port)
        print(f"metrics: http://127.0.0.1:{args.metrics_port}/metrics")
    if args.warmup:
        n = eng.warmup(max_prompt_len=args.prompt_len)
        print(f"warmup: {n} programs compiled")
    prompts = [
        rng.integers(0, cfg.vocab_size, int(rng.integers(4, args.prompt_len + 1)))
        for _ in range(args.requests)
    ]
    if args.arrival_rate > 0:
        # live Poisson replay: requests join the window at their arrival
        # times and the engine ticks between joins (continuous batching)
        at = np.cumsum(rng.exponential(1.0 / args.arrival_rate, args.requests))
        at -= at[0]
        i, t0 = 0, time.perf_counter()
        while i < len(prompts) or not eng.scheduler.idle:
            now = time.perf_counter() - t0
            while i < len(prompts) and at[i] <= now:
                eng.submit(prompts[i], args.max_new)
                i += 1
            if not eng.scheduler.idle:
                eng.tick()
            elif i < len(prompts):
                time.sleep(min(1e-3, max(0.0, at[i] - now)))
        eng.stats.wall_s += time.perf_counter() - t0
        done = eng.scheduler.completed
    else:
        for p in prompts:
            eng.submit(p, args.max_new)
        done = eng.run()
    for r in done:
        print(f"req {r.uid}: prompt_len={len(r.prompt)} -> {r.output}")
    print("stats:", _measured(eng.summary()))
    if metrics_server is not None:
        # self-scrape once so CI can assert the exposition round-trips
        from urllib.request import urlopen
        body = urlopen(
            f"http://127.0.0.1:{args.metrics_port}/metrics"
        ).read().decode()
        print(f"metrics: scraped {len(body.splitlines())} exposition lines")
        metrics_server.shutdown()
    if tracer is not None:
        tracer.write(args.trace_out)
        print(f"trace: {len(tracer)} events -> {args.trace_out}")


if __name__ == "__main__":
    main()
