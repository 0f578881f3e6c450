"""Docs/tooling drift checks: the commands ROADMAP.md documents must exist in
the Makefile with the shapes it claims, the architecture map must exist and be
linked, and the examples must demonstrate the current engine flags — so the
docs surface cannot silently rot as hot paths evolve."""
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _read(rel: str) -> str:
    return (ROOT / rel).read_text()


def test_makefile_targets_match_roadmap():
    """Every make target ROADMAP documents exists; the tier-1 invocation in
    the Makefile is the one ROADMAP pins; ci includes the smokes ROADMAP
    promises."""
    roadmap = _read("ROADMAP.md")
    makefile = _read("Makefile")
    for target in ("tier1", "ci", "bench", "bench-decode",
                   "smoke-int4", "smoke-prefill", "smoke-serve-cb",
                   "smoke-prefetch", "smoke-trace", "smoke-sample"):
        assert f"make {target}" in roadmap or f"`{target}`" in roadmap, (
            f"ROADMAP no longer documents the `{target}` make target"
        )
        assert re.search(rf"^{target}:", makefile, re.M), (
            f"ROADMAP documents `make {target}` but the Makefile has no "
            f"such target"
        )
    # the tier-1 gate is the plain pytest invocation ROADMAP pins
    assert "python -m pytest -x -q" in roadmap
    assert "pytest -x -q" in makefile
    assert "tier1_delta.py" in makefile          # the delta print ROADMAP cites
    # ci = dev-deps + tier1 + both smokes, as ROADMAP claims
    ci_line = re.search(r"^ci:\s*(.+?)(?:\s*##|$)", makefile, re.M).group(1)
    for dep in ("dev-deps", "tier1", "smoke-int4", "smoke-prefill",
                "smoke-serve-cb", "smoke-prefetch", "smoke-trace",
                "smoke-sample"):
        assert dep in ci_line, (dep, ci_line)
    # bench-decode rows ROADMAP/benchmarks README describe are actually passed
    assert "--spec-k" in makefile and "--quantization" in makefile


def test_architecture_doc_exists_and_is_linked():
    assert (ROOT / "docs" / "ARCHITECTURE.md").exists()
    roadmap = _read("ROADMAP.md")
    assert "docs/ARCHITECTURE.md" in roadmap
    arch = _read("docs/ARCHITECTURE.md")
    # the load-bearing sections: residency model, dispatch table, exactness,
    # quantized link, serving tick
    for needle in ("SlotStore", "SlotLUT", "DemandPredictor", "dispatch",
                   "int4", "replay", "ServingEngine", "prefill",
                   "KVPagePool", "page table", "continuous batching",
                   "shadow generation", "prefetch", "flip", "relaunch",
                   "write-through",
                   # the observability section: tracks/lanes map, the
                   # span->machine mapping, and the auditor invariant list
                   "Tracer", "Perfetto", "auditor", "prefetch_ship",
                   "kv_use", "MetricsRegistry", "Prometheus",
                   "one launch", "trace-out",
                   # sampled speculative serving: PRNG protocol, the accept
                   # rule, and the distributional-exactness story
                   "stochastic_accept", "fold_in", "warp_probs",
                   "chi-squared", "min(1, q(t)/p(t))", "smoke-sample"):
        assert needle.lower() in arch.lower(), needle


def test_benchmarks_readme_documents_the_json():
    readme = _read("benchmarks/README.md")
    for needle in ("BENCH_decode.json", "mb_per_token", "0.30",
                   "ttft", "prefill_fused", "tier1",
                   "BENCH_serving.json", "serving_load", "goodput",
                   "ttft_p99", "arrival",
                   "fused_rotary_pf", "overlap_ms", "relaunched_steps",
                   "prefetch_wasted_bytes", "1.5x",
                   # tracing/metrics flags + the tracing-overhead row
                   "--trace-out", "--metrics-port", "trace_overhead_ratio",
                   "repro.obs", "3%",
                   # the sampled *_t row family and its gate
                   "spec4_rotary_hi_t", "accept_rate", "1.4x"):
        assert needle.lower() in readme.lower(), needle


def test_examples_show_current_flags():
    """The examples demonstrate the flags the engines actually take today."""
    quick = _read("examples/quickstart.py")
    serve = _read("examples/serve_rotary.py")
    for needle in ("prefill_chunk", "spec_k", "int4", "per_layer_table"):
        assert needle in quick, needle
    for needle in ("spec_cap", "bucketed_prefill", "int4",
                   "kv_page_size", "ttft_p50_ms", "per_layer_table"):
        assert needle in serve, needle
    # and those kwargs really exist on the engines (drift in the other
    # direction: examples naming parameters that were renamed away)
    import inspect

    from repro.core import RotaryEngine
    from repro.serving import ServingEngine

    rotary_params = inspect.signature(RotaryEngine.__init__).parameters
    for kw in ("prefill_chunk", "spec_k", "host_routing", "fused_decode",
               "prefetch", "trace"):
        assert kw in rotary_params, kw
    serving_params = inspect.signature(ServingEngine.__init__).parameters
    for kw in ("spec_cap", "bucketed_prefill", "residency",
               "paged", "kv_pages", "kv_page_size", "prefetch", "trace"):
        assert kw in serving_params, kw


def test_serve_cli_flags_exist():
    """The CLI flags the docs/Makefile reference parse (smoke the argparse
    wiring without running a model)."""
    serve_src = _read("src/repro/launch/serve.py")
    for flag in ("--size", "--layers",
                 "--prefill-chunk", "--spec-k", "--spec-cap",
                 "--quantization", "--quant-group",
                 "--arrival-rate", "--kv-pages", "--kv-page-size",
                 "--prefetch", "--trace-out", "--metrics-port",
                 "--temperature", "--top-k", "--top-p", "--sample-seed"):
        assert flag in serve_src, flag
    makefile = _read("Makefile")
    assert "--prefill-chunk" in makefile          # smoke-prefill really uses it
    assert "--quantization int4" in makefile      # smoke-int4 really uses it
    assert "--arrival-rate" in makefile           # smoke-serve-cb really uses it
    assert "--prefetch" in makefile               # smoke-prefetch really uses it
    assert "--trace-out" in makefile              # smoke-trace really uses it
    assert "--metrics-port" in makefile           # smoke-trace scrapes it
    assert "repro.obs" in makefile                # the auditor runs on the artifact
    assert "trace_view.py" in makefile            # the top-N span table prints
    assert "--temperature 0.8" in makefile        # smoke-sample really samples
    assert "--sample-seed" in makefile            # ... with a pinned seed
    assert "accept_rate" in makefile              # ... and asserts telemetry


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache goes to
    the checkout's fixed .jax_cache. (config.update is intercepted: tests
    never turn the persistent cache on.)"""
    import jax

    from repro.launch import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.place_compile_cache() == "/elsewhere/cache"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.place_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    assert ".jax_cache/" in _read(".gitignore")
