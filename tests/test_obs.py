"""Observability subsystem: tracer, metrics registry, contract auditor.

Unit coverage builds traces/metrics by hand (no model); integration coverage
captures REAL traces from every traced mode — fused decode, spec-K windows,
chunked prefill, asynchronous prefetch, continuous-batching serving — and
replays each through the auditor, plus the overlap_ms spans-vs-stats
regression and the per-layer stats table.
"""
import json

import numpy as np
import pytest

from repro.config import ResidencyConfig
from repro.core import RotaryEngine
from repro.models.transformer import Runtime
from repro.obs import (
    MACHINE_TRACKS,
    AuditError,
    MetricsRegistry,
    Tracer,
    audit,
    resolve_tracer,
)
from repro.obs.metrics import Histogram
from repro.serving import ServingEngine

from conftest import params_for


# ===========================================================================
# tracer unit coverage
# ===========================================================================
def test_tracer_span_instant_unit_and_export():
    tr = Tracer()
    u = tr.new_unit("decode")
    assert u == 1 and tr.unit == 1
    with tr.span("launch", "launch", args={"k": 2}):
        pass
    tr.complete("pull", "pull", 1.0, 1.5)
    tr.instant("miss", "launch", args={"layers": 3})
    tr.complete("queued", "request", 0.0, 0.25, lane=7)
    out = tr.chrome_trace()
    evs = [e for e in out["traceEvents"] if e["ph"] != "M"]
    meta = [e for e in out["traceEvents"] if e["ph"] == "M"]
    # every machine track is named in display order; request lane 7 is named
    names = {(m["pid"], m.get("tid")): m["args"]["name"] for m in meta
             if m["name"] == "thread_name"}
    for i, track in enumerate(MACHINE_TRACKS):
        assert names[(1, i)] == track
    assert names[(2, 7)] == "request 7"
    # spans carry dur, instants carry scope, all carry the unit in args
    span = next(e for e in evs if e["name"] == "launch")
    assert span["ph"] == "X" and span["dur"] >= 0
    assert span["args"]["unit"] == 1 and span["args"]["k"] == 2
    inst = next(e for e in evs if e["name"] == "miss")
    assert inst["ph"] == "i" and inst["s"] == "t"
    lane = next(e for e in evs if e["name"] == "queued")
    assert lane["pid"] == 2 and lane["tid"] == 7
    # the export is valid JSON end to end (what Perfetto actually parses)
    json.loads(json.dumps(out))


def test_tracer_ring_capacity_bounds_memory():
    tr = Tracer(capacity=10)
    for i in range(100):
        tr.instant("tick", "launch", args={"i": i})
    assert len(tr) == 10
    # oldest records dropped: the survivors are the 10 newest
    kept = [r[7]["i"] for r in tr.records()]
    assert kept == list(range(90, 100))


def test_resolve_tracer_normalises_disabled_to_none():
    assert resolve_tracer(None) is None
    assert resolve_tracer(Tracer(enabled=False)) is None
    tr = Tracer()
    assert resolve_tracer(tr) is tr


# ===========================================================================
# metrics unit coverage
# ===========================================================================
def test_histogram_percentiles_match_numpy():
    h = Histogram("x_ms")
    xs = np.random.default_rng(0).uniform(0.1, 900.0, 500)
    for v in xs:
        h.observe(v)
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(np.percentile(xs, q))
    assert h.mean == pytest.approx(xs.mean())
    h.reset()
    assert h.count == 0 and h.percentile(50) == 0.0


def test_registry_exposition_prometheus_format():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests").inc(3)
    reg.gauge("pages_free").set(12)
    h = reg.histogram("lat_ms", "latency", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    reg.set_from({"hit_rate": 0.9, "label": "ignored-non-numeric"})
    text = reg.exposition()
    assert "# TYPE req_total counter\nreq_total 3" in text
    assert "pages_free 12" in text
    assert "engine_hit_rate 0.9" in text
    assert "engine_label" not in text
    # cumulative bucket counts + the +Inf catch-all, sum and count
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="10"} 2' in text
    assert 'lat_ms_bucket{le="+Inf"} 3' in text
    assert "lat_ms_sum 55.5" in text and "lat_ms_count 3" in text
    summ = reg.summary()
    assert summ["req_total"] == 3
    assert summ["lat_ms"]["count"] == 3


def test_serve_metrics_http_scrape():
    from urllib.request import urlopen

    from repro.obs import serve_metrics

    reg = MetricsRegistry()
    reg.counter("scrapes").inc()
    server = serve_metrics(lambda: reg, 0)        # port 0: ephemeral
    try:
        port = server.server_address[1]
        body = urlopen(f"http://127.0.0.1:{port}/metrics").read().decode()
        assert "scrapes 1" in body
        with pytest.raises(Exception):
            urlopen(f"http://127.0.0.1:{port}/nope")
    finally:
        server.shutdown()


# ===========================================================================
# auditor unit coverage: hand-built violating traces are rejected
# ===========================================================================
def _ev(name, ts, dur=None, unit=1, cat="launch", **args):
    e = {"ph": "X" if dur is not None else "i", "name": name, "pid": 1,
         "tid": 0, "ts": ts, "cat": cat, "args": {"unit": unit, **args}}
    if dur is not None:
        e["dur"] = dur
    return e


def _clean_unit(unit=1, t0=0.0):
    return [
        _ev("launch", t0, 100.0, unit),
        _ev("prefetch_ship", t0 + 10, 20.0, unit, cat="prefetch"),
        _ev("pull", t0 + 110, 50.0, unit, cat="pull"),
        _ev("rotation", t0 + 170, 30.0, unit, cat="rotation"),
    ]


def test_audit_accepts_clean_trace():
    rep = audit(_clean_unit(1) + _clean_unit(2, 1000.0))
    assert rep.ok and rep.units_checked == 2 and rep.miss_free_units == 2
    assert rep.overlap_ms == pytest.approx(0.04)  # 2 x 20us ship spans


def test_audit_rejects_double_pull_per_window():
    evs = _clean_unit() + [_ev("pull", 200.0, 10.0, cat="pull")]
    rep = audit(evs)
    assert not rep.ok
    assert any("2 primary pulls" in v for v in rep.violations)
    with pytest.raises(AuditError):
        rep.raise_for_violations()


def test_audit_rejects_rotation_mid_window():
    # rotation dispatched BEFORE the queue-draining pull = racing the window
    evs = [
        _ev("launch", 0.0, 100.0),
        _ev("rotation", 50.0, 30.0, cat="rotation"),
        _ev("pull", 110.0, 50.0, cat="pull"),
    ]
    rep = audit(evs)
    assert any("mid-window" in v for v in rep.violations)


def test_audit_rejects_prefetch_outside_overlap_window():
    # ship starts before the launch
    early = [
        _ev("prefetch_ship", 0.0, 5.0, cat="prefetch"),
        _ev("launch", 10.0, 100.0),
        _ev("pull", 120.0, 50.0, cat="pull"),
    ]
    assert any("before the launch" in v for v in audit(early).violations)
    # ship overruns the pull (not hidden under compute at all)
    late = [
        _ev("launch", 0.0, 100.0),
        _ev("prefetch_ship", 90.0, 200.0, cat="prefetch"),
        _ev("pull", 110.0, 50.0, cat="pull"),
    ]
    assert any("overruns the pull" in v for v in audit(late).violations)


def test_audit_rejects_kv_page_use_after_free():
    evs = [
        _ev("kv_ensure", 0.0, None, cat="kv_pool", uid=1, pages=[3, 4]),
        _ev("kv_use", 10.0, None, cat="kv_pool", pages=[3, 4]),
        _ev("kv_release", 20.0, None, cat="kv_pool", uid=1, pages=[3, 4]),
        _ev("kv_use", 30.0, None, cat="kv_pool", pages=[4]),
    ]
    rep = audit(evs)
    assert rep.kv_events == 4
    assert any("after release" in v for v in rep.violations)
    # double release is also flagged
    rep2 = audit(evs[:3] + [
        _ev("kv_release", 40.0, None, cat="kv_pool", uid=1, pages=[3])])
    assert any("double release" in v for v in rep2.violations)


def test_audit_exempts_units_with_misses_and_relaunches():
    # a missed unit legitimately carries extra launches/pulls (relaunch or
    # replay) — exempt from the count, still ordering-checked
    evs = [
        _ev("launch", 0.0, 100.0),
        _ev("miss", 105.0, None),
        _ev("pull", 110.0, 50.0, cat="pull"),
        _ev("launch", 200.0, 40.0, kind="relaunch"),
        _ev("pull", 250.0, 10.0, cat="pull", kind="relaunch"),
        _ev("rotation", 270.0, 30.0, cat="rotation"),
    ]
    rep = audit(evs)
    assert rep.ok and rep.miss_free_units == 0 and rep.units_checked == 1


# ===========================================================================
# integration: real traces from every traced mode pass the auditor
# ===========================================================================
def _trace_rotary(cfg, params, *, steps=4, tr=None, **kw):
    tr = tr if tr is not None else Tracer()
    eng = RotaryEngine(
        cfg, params, ResidencyConfig(mode="rotary", num_slots=6),
        rt=Runtime(cache_len=64), batch=1, trace=tr, **kw,
    )
    prompt = (np.random.default_rng(0)
              .integers(0, cfg.vocab_size, (1, 8)).astype(np.int32))
    eng.generate(prompt, steps)
    return eng, tr


@pytest.mark.parametrize("mode_kw", [
    {},                               # fused single-token decode
    {"spec_k": 2},                    # speculative windows
    {"prefill_chunk": 8},             # chunked prefill
    {"prefetch": True, "spec_k": 2},  # async prefetch under spec windows
])
def test_real_traces_pass_auditor(mode_kw):
    cfg, params = params_for("qwen2-moe-a2.7b")
    eng, tr = _trace_rotary(cfg, params, **mode_kw)
    rep = audit(tr)
    rep.raise_for_violations()
    assert rep.units_checked > 0 and rep.launches > 0 and rep.pulls > 0
    assert rep.rotations > 0
    if mode_kw.get("prefetch"):
        assert rep.prefetch_spans > 0


def test_cb_serving_trace_passes_auditor_with_lanes(tmp_path):
    cfg, params = params_for("qwen2-moe-a2.7b")
    tr = Tracer()
    eng = ServingEngine(
        cfg, params,
        residency=ResidencyConfig(mode="rotary", num_slots=6),
        rt=Runtime(cache_len=64), num_slots=2, spec_cap=2,
        kv_pages=16, kv_page_size=8, trace=tr,
    )
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32),
                       max_new=4) for _ in range(3)]
    eng.run()
    rep = audit(tr)
    rep.raise_for_violations()
    assert rep.kv_events > 0                       # the paged pool was traced
    # the exported file is Perfetto-loadable and shows one lane per request
    path = tmp_path / "cb.json"
    tr.write(path)
    out = json.load(open(path))
    lanes = {e["tid"] for e in out["traceEvents"]
             if e.get("pid") == 2 and e["ph"] != "M"}
    assert lanes == {r.uid for r in reqs}
    # each lane carries the full lifecycle: queued -> prefill -> decode/finish
    for r in reqs:
        names = {e["name"] for e in out["traceEvents"]
                 if e.get("pid") == 2 and e.get("tid") == r.uid}
        assert {"queued", "prefill", "finish"} <= names


def test_overlap_ms_spans_agree_with_legacy_stats():
    # miss-starved prefetch run: the prefetch_ship spans cover exactly the
    # interval the manager's wall-clock side channel accumulates, so the
    # span-derived overlap must agree with EngineStats.overlap_ms
    cfg, params = params_for("qwen2-moe-a2.7b")
    eng, tr = _trace_rotary(cfg, params, steps=6, prefetch=True)
    stats_ms = eng.stats.overlap_ms
    span_ms = audit(tr).overlap_ms
    assert stats_ms > 0
    assert span_ms == pytest.approx(stats_ms, rel=0.01, abs=1.0)
    # the export's rounded microseconds lose nothing the check could see
    recorded_ms = 1e3 * sum(r[5] for r in tr.records()
                            if r[1] == "prefetch_ship")
    assert span_ms == pytest.approx(recorded_ms, abs=0.01)


# ===========================================================================
# step spans: every fused decode step and prefill chunk is one named span
# ===========================================================================
# The phases inside a unit, in the order they start.
PHASES = ("launch", "pull", "telemetry", "account", "rotation")
FORWARDED_PREFIX = "engine/"


def _units(tr):
    """{unit: (kind, [records])} of every unit the engine opened."""
    kinds = {r[6]: r[7]["kind"] for r in tr.records() if r[1] == "unit"}
    out = {u: (k, []) for u, k in kinds.items()}
    for r in tr.records():
        if r[6] in out and r[1] != "unit":
            out[r[6]][1].append(r)
    return out


def _chunked():
    cfg, params = params_for("qwen2-moe-a2.7b")
    return _trace_rotary(cfg, params, prefill_chunk=8)


def test_every_decode_and_chunk_unit_has_one_step_span():
    _, tr = _chunked()
    units = _units(tr)
    assert {k for k, _ in units.values()} == {"chunk", "decode"}
    for u, (kind, recs) in units.items():
        steps = [r for r in recs if r[1] == "step"]
        assert len(steps) == 1, (u, kind, len(steps))
        assert steps[0][0] == "X" and steps[0][7] == {"kind": kind}


def test_unit_spans_lie_inside_their_step_in_phase_order():
    _, tr = _chunked()
    for u, (kind, recs) in _units(tr).items():
        (step,) = [r for r in recs if r[1] == "step"]
        s0, s1 = step[4], step[4] + step[5]
        for r in recs:
            assert s0 <= r[4] and r[4] + r[5] <= s1, (u, r[1])
        first = {}
        for r in recs:
            first.setdefault(r[1], r[4])
        names = {r[1] for r in recs}
        assert set(PHASES) <= names, (u, kind, names)
        assert [first[n] for n in PHASES] == sorted(first[n] for n in PHASES)
        assert "view" in names and ("demand" in names) == (kind == "chunk")
        # view nests inside the launch, and (decode) sample precedes it
        (launch,) = [r for r in recs if r[1] == "launch"]
        for v in (r for r in recs if r[1] == "view"):
            assert launch[4] <= v[4] and v[4] + v[5] <= launch[4] + launch[5]
        if kind == "decode":
            assert first["sample"] <= first["launch"]
        if kind == "chunk":
            assert first["account"] <= first["demand"] <= first["rotation"]


def test_chunk_telemetry_spans_carry_picks_and_rows():
    """Each fused chunk's ``telemetry`` span carries the chunk's picks and
    the expert weight planes its MoE layers read (what
    ``expert_rows_per_pick`` reads); decode units' carry nothing."""
    eng, tr = _chunked()
    chunks = 0
    for kind, recs in _units(tr).values():
        for r in recs:
            if r[1] != "telemetry":
                continue
            if kind == "chunk":
                chunks += 1
                assert r[7]["picks"] == (eng.num_moe_layers * 8
                                         * eng.cfg.moe.top_k)
                assert 0 < r[7]["rows"] <= r[7]["picks"]
            else:
                assert not r[7]
    assert chunks == 1


def test_spans_are_forwarded_to_the_profiler_host_plane(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    cfg, params = params_for("qwen2-moe-a2.7b")
    eng, tr = _trace_rotary(cfg, params, prefill_chunk=8)   # compiled here
    n0 = len(tr)
    prompt = (np.random.default_rng(1)
              .integers(0, cfg.vocab_size, (1, 8)).astype(np.int32))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.generate(prompt, 3)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [ev.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events]
    forwarded = sorted(n[len(FORWARDED_PREFIX):] for n in names
                       if n.startswith(FORWARDED_PREFIX))
    spans = sorted(r[1] for r in tr.records()[n0:] if r[0] == "X")
    assert spans and forwarded == spans
    assert {"step", "view", "launch", "pull", "telemetry", "account",
            "demand", "sample", "rotation", "upload"} <= set(spans)
    # the benchmark selects its own annotations by these exact names
    assert not {"window", "prefill", "decode"} & set(names)


def test_tracing_off_is_structurally_free():
    # trace=None and a disabled tracer both normalise to NO tracer reference:
    # the hot path executes identical instructions and emits nothing
    cfg, params = params_for("qwen2-moe-a2.7b")
    dis = Tracer(enabled=False)
    eng_off, _ = _trace_rotary(cfg, params, tr=dis)
    assert eng_off._tr is None and eng_off.tracer is None
    assert len(dis) == 0


# ===========================================================================
# per-layer stats + metrics-backed latency summary
# ===========================================================================
def test_per_layer_table_matches_aggregate():
    cfg, params = params_for("qwen2-moe-a2.7b")
    eng, _ = _trace_rotary(cfg, params)
    rows = eng.stats.per_layer()
    assert [r["layer"] for r in rows] == sorted(eng.stats.layers)
    assert sum(r["misses"] for r in rows) == eng.stats.misses
    assert sum(r["hits"] for r in rows) == eng.stats.hits
    table = eng.stats.per_layer_table()
    assert "hit_rate" in table.splitlines()[0]
    assert len(table.splitlines()) == len(rows) + 1


def test_latency_summary_matches_legacy_percentiles():
    cfg, params = params_for("qwen2-moe-a2.7b")
    eng = ServingEngine(
        cfg, params, rt=Runtime(cache_len=64), num_slots=2, spec_cap=2,
        kv_pages=16, kv_page_size=8,
    )
    rng = np.random.default_rng(0)
    for _ in range(3):
        eng.submit(rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32),
                   max_new=4)
    eng.run()
    out = eng.latency_summary()
    assert out == eng.latency_summary()            # idempotent (reset+rebuild)
    # the metrics-backed percentiles reproduce the legacy np.percentile math
    done = eng.scheduler.completed
    ttft = [r.first_token_at - r.submitted_at for r in done if r.first_token_at]
    itl = [b - a for r in done
           for a, b in zip(r.token_times, r.token_times[1:])]
    assert out["completed"] == len(done) == 3
    assert out["ttft_p50_ms"] == pytest.approx(
        1e3 * np.percentile(ttft, 50), abs=1e-3)
    assert out["itl_p99_ms"] == pytest.approx(
        1e3 * np.percentile(itl, 99), abs=1e-3)
    # and the same histograms surface in the Prometheus exposition
    text = eng.metrics_registry().exposition()
    assert "ttft_ms_bucket" in text and "itl_ms_count" in text
    assert "engine_hit_rate" in text
