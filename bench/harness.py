"""Runs one cell of ``BENCHMARK.json`` once: set-up, a measured window, the
check against the plain reference, and one result line.

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric is a file found by its name (``find``): ``configs/<config>.json``,
``traffic/<traffic>.json``, ``cells/<workload>.json`` and
``metrics/<metric>.py`` (a module with ``read(ctx) -> float | None``), looked
up in each of ``roots`` in turn. Adding any of them adds files and entries,
and edits none.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
ANNOTATIONS = ("window", "prefill", "decode")
KINDS = {"configs": ".json", "traffic": ".json", "cells": ".json",
         "metrics": ".py"}
SAMPLE_SESSIONS = 8           # served requests the reference re-runs
SAMPLE_POSITIONS = 8192       # and at most this many positions in all


class NoChip(SystemExit):
    pass


def find(kind: str, name: str, roots: Sequence[str] = (BENCH,)) -> str:
    """Path of the ``kind`` file named ``name`` in the first root that has it."""
    for root in roots:
        path = os.path.join(root, kind, name + KINDS[kind])
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {kind} file {name!r} under {list(roots)}")


def load_json(kind: str, name: str, roots: Sequence[str] = (BENCH,)) -> Dict:
    with open(find(kind, name, roots)) as f:
        return json.load(f)


def load_metric(name: str, roots: Sequence[str] = (BENCH,)):
    """The ``read`` function of per-layer metric ``name``."""
    path = find("metrics", name, roots)
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def benchmark(path: str = os.path.join(CHECKOUT, "BENCHMARK.json")) -> Dict:
    with open(path) as f:
        return json.load(f)


def metrics_of(bench: Dict, workload: str, key: str) -> List[Dict]:
    """The ``key`` metrics (end_to_end / per_layer) that ``workload`` reports."""
    return [m for m in bench[key]
            if "workloads" not in m or workload in m["workloads"]]


def require_tpu(chips: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(
            f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s) ({devs[0].device_kind}); "
            "nothing was measured")


def place_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed directory of the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every
    program however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Compiles:
    """Counts programs compiled, and of them those loaded from the persistent
    compilation cache."""

    def __init__(self):
        import jax

        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def memory() -> Dict[str, int]:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {k: int(stats.get(k, 0)) for k in ("bytes_in_use", "peak_bytes_in_use")}


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _counters(eng) -> Dict[str, float]:
    s = eng.stats
    return {"device_dispatches": s.device_dispatches, "misses": s.misses,
            "hits": s.hits, "bytes_uploaded": s.bytes_uploaded,
            "replayed_steps": s.replayed_steps, "replay_pulls": s.replay_pulls,
            "sync_pulls": s.sync_pulls, "prefill_chunks": s.prefill_chunks,
            "prefill_replays": s.prefill_replays}


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def window_numbers(requests) -> Dict:
    """Output tokens, inter-token gaps and first-token times of the window's
    requests."""
    tokens, gaps, ttft = 0, [], []
    for r in requests:
        tokens += len(r.token_times)
        gaps.extend(np.diff(r.token_times).tolist())
        if r.token_times:
            ttft.append(r.token_times[0] - r.sent)
    return {"tokens": tokens, "gaps": gaps, "ttft": ttft}


def work(requests, chunk: int, s) -> List:
    """(FLOPs, bytes) of every launch's worth of work served in the window:
    each prefill chunk and each decode step."""
    from bench import counts
    from repro.core.engine import prefill_chunk_plan

    out = []
    for r in requests:
        if r.token_times:
            start, plan = 0, prefill_chunk_plan(len(r.prompt), chunk)
            for i, c in enumerate(plan):
                out.append(counts.chunk(s, start, c, head=i == len(plan) - 1))
                start += c
        out.extend(counts.chunk(s, ctx - 1, 1, head=True) for ctx in r.contexts)
    return out


def sample_sessions(requests, seed: int) -> List[Dict]:
    """Sessions for the reference: the longest, then others drawn by the
    seed, up to ``SAMPLE_SESSIONS`` and ``SAMPLE_POSITIONS``."""
    done = [r for r in requests if r.served and not r.failed]
    if not done:
        return []
    done.sort(key=lambda r: -len(r.served))
    rest = done[1:]
    order = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7]).permutation(len(rest))
    picked, total = [], 0
    for r in [done[0]] + [rest[i] for i in order]:
        n = len(r.prompt) + len(r.served)
        if picked and (len(picked) >= SAMPLE_SESSIONS
                       or total + n > SAMPLE_POSITIONS):
            break
        picked.append({"prompt": r.prompt, "served": r.served})
        total += n
    return picked


def check(gaps: np.ndarray, limits: Dict) -> Dict:
    """The numbers compared, each with its limit (None: read, not held)."""
    gaps = np.asarray(gaps, np.float64)
    readings = {
        "widest_gap": float(gaps.max()) if gaps.size else None,
        "mean_gap": float(gaps.mean()) if gaps.size else None,
        "served_compared": int(gaps.size),
    }
    return {k: {"value": v, "limit": limits.get(k)} for k, v in readings.items()}


def correct_of(checks: Dict) -> bool:
    held = [c for c in checks.values() if c["limit"] is not None]
    return bool(held) and all(c["value"] is not None and c["value"] <= c["limit"]
                              for c in held)


def run_cell(workload: Dict, seed: int, seconds: float, trace: bool, *,
             roots: Sequence[str] = (BENCH,), bench: Optional[Dict] = None,
             chips_check: bool = True, control: Optional[str] = None,
             t_start: Optional[float] = None, fault=None) -> Dict:
    """One run of one cell; returns the result line (a dict). Tests skip the
    look for a chip (``chips_check``) and may break the engine (``fault``)."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    from bench import counts, loadgen, program, trace as trace_mod, weights
    from bench.reference import moe_lm
    from bench.shapes import Shapes

    if chips_check:
        require_tpu(workload["chips"])
    place_compile_cache()
    compiles = Compiles()
    bench = bench if bench is not None else benchmark()
    conf = load_json("configs", workload["config"], roots)
    cell = load_json("cells", workload["name"], roots)
    traffic = load_json("traffic", workload["traffic"], roots)
    s = Shapes.of(conf)
    cfg = program.model_config(conf)
    dev = jax.devices()[0]
    log = lambda *a: print(*a, file=sys.stderr, flush=True)

    # ---- set-up: weights, engine, the cell's own shapes ------------------
    t0 = time.perf_counter()
    w = weights.make_weights(s, seed, rows=program.expert_rows(cfg),
                             dtype=conf["torch_dtype"])
    jax.block_until_ready(w)
    t_init = time.perf_counter()
    tracer = None
    if trace:
        from repro.obs import Tracer
        tracer = Tracer(capacity=2_000_000)
    eng = program.build_engine(cfg, program.params(cfg, w), cell, seed, tracer)
    if fault is not None:
        fault(eng)
    t_build = time.perf_counter()
    server = loadgen.Server(eng, s.vocab, _annotate)
    warm = loadgen.closed_loop(server, traffic, seed, float("inf"),
                               start_index=1 << 20,
                               max_requests=loadgen.WARMUP_REQUESTS)
    jax.effects_barrier()
    t_warm = time.perf_counter()
    setup_s = t_warm - t_start
    log(f"bench: setup {setup_s:.3f} s = start {t0 - t_start:.3f} + init "
        f"{t_init - t0:.3f} + engine build {t_build - t_init:.3f} + warm-up "
        f"{t_warm - t_build:.3f}; compiles so far {compiles.n}")

    # ---- the measured window ---------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    before = _counters(eng)
    n_compiles, n_hits = compiles.n, compiles.hits
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # annotations and runtime only
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    w0 = time.perf_counter()
    with _annotate("window"):
        out = loadgen.closed_loop(server, traffic, seed, w0 + seconds)
    requests = out["requests"]
    w1 = max([w0] + [t for r in requests for t in r.token_times])
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = compiles.n - n_compiles
    hits_in_window = compiles.hits - n_hits
    mem = memory()
    delta = {k: v - before[k] for k, v in _counters(eng).items()}
    spans = ([r for r in tracer.records() if w0 <= r[4] <= w1]
             if tracer is not None else [])
    attempted, failed = out["attempted"], out["failed"] + warm["failed"]
    win = window_numbers(requests)
    window_s = w1 - w0
    log(f"bench: window {window_s:.3f} s, {win['tokens']} tokens, "
        f"{len(requests)} requests, {attempted} attempted, {failed} failed, "
        f"compiles in window {compiles_in_window} ({hits_in_window} from the "
        f"cache), counters {delta}")

    # ---- free the program's state, then the reference --------------------
    del eng, server
    gc.collect()
    t_ref = time.perf_counter()
    sessions = sample_sessions(requests, seed)
    gaps = moe_lm.served_gaps(s, w, sessions)
    checks = check(gaps, cell["limits"])
    if control == "lower":
        lower = moe_lm.LOWER[conf["torch_dtype"]]
        checks.update({f"control_{k}": v for k, v in check(
            moe_lm.served_gaps(s, w, sessions, quant=lower),
            cell["limits"]).items()})
    log(f"bench: reference over {len(sessions)} sessions "
        f"({sum(len(x['served']) for x in sessions)} served tokens) in "
        f"{time.perf_counter() - t_ref:.3f} s")

    # ---- metrics -----------------------------------------------------------
    e2e = {
        "output_tok_s": win["tokens"] / window_s,
        "itl_p95_ms": percentile(win["gaps"], 95) * 1e3 if win["gaps"] else None,
        "ttft_p50_ms": (statistics.median(win["ttft"]) * 1e3 if win["ttft"]
                        else None),
        "hbm_peak_gb": mem["peak_bytes_in_use"] / 1e9,
        "setup_s": setup_s,
    }
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": mem["peak_bytes_in_use"]}
    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": device}
    if not trace:
        for m in metrics_of(bench, workload["name"], "end_to_end"):
            value = e2e[m["name"]]
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        devices, host = trace_mod.events(trace_mod.find_xplane(trace_dir),
                                         ANNOTATIONS)
        red = trace_mod.reduce(devices, host)
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        log(f"bench: idle by host span {red['idle_by_span']}")
        ops0 = devices[min(devices)]
        ctx = SimpleNamespace(
            shapes=s, cell=cell, window_s=window_s, tokens=win["tokens"],
            counters=delta, spans=spans, memory=mem, trace=red,
            ops=ops0, trace_window=(red["w0"], red["w1"]),
            work=work(requests, cell["prefill_chunk"], s),
            peak=counts.peaks(dev.device_kind))
        for m in metrics_of(bench, workload["name"], "per_layer"):
            value = load_metric(m["name"], roots)(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    held = {k: v for k, v in checks.items() if not k.startswith("control_")}
    result["correct"] = correct_of(held) and failed == 0
    if control is not None:
        result["control_correct"] = correct_of(
            {k: v for k, v in checks.items() if k.startswith("control_")})
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    return result
