"""The trace reduction: device busy union, kernel time by name and idle gaps
named by the host span active in them, on made-up intervals and on a small
trace recorded on one TPU v5e (``data/v5e_small.xplane.pb``: a top-k gate
tile of [16, 60] under ``prefill`` with a matmul, one of [1, 128] under
``decode``, three times inside a ``window`` span)."""
import os
from types import SimpleNamespace

import pytest

import benchpath  # noqa: F401

from bench import counts, harness, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "v5e_small.xplane.pb")


def ev(name, a, b, detail=""):
    return (name, float(a), float(b), detail)


def test_union_merges_overlaps_and_clips_to_the_window():
    ops = [ev("a", 0, 10), ev("b", 5, 20), ev("c", 30, 40), ev("d", 38, 45),
           ev("e", 90, 120)]
    assert trace.union(ops, 2, 100) == [(2, 20), (30, 45), (90, 100)]
    assert trace.busy_ns(ops, 2, 100) == 18 + 15 + 10


def test_idle_gaps_are_named_by_the_innermost_host_span():
    ops = [ev("k", 10, 20), ev("k", 50, 60)]
    host = [ev("window", 0, 100), ev("request", 0, 100), ev("prefill", 0, 30),
            ev("decode", 40, 70)]
    gaps = trace.idle_gaps(ops, host, 0, 100)
    assert [n for n, _ in gaps] == ["request", "request", "prefill"]
    assert [s for _, s in gaps] == pytest.approx([40e-9, 30e-9, 10e-9])
    assert sum(s for _, s in gaps) == pytest.approx(80e-9)


def test_reduce_accounts_for_the_whole_window():
    devs = {0: [ev("fusion", 10, 20), ev("topk_gate", 25, 26), ev("fusion", 26, 40)]}
    host = [ev("window", 0, 100), ev("decode", 5, 45)]
    red = trace.reduce(devs, host)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(25e-9)
    idle = sum(s for _, s in red["idle_gaps"])
    assert red["busy_s"] + idle == pytest.approx(red["window_s"])
    assert red["device_ops"][0] == ["fusion", pytest.approx(24e-9)]
    assert trace.matching(devs[0], "topk_gate", 0, 100) == [devs[0][1]]


def test_clock_offset_lies_between_each_launch_and_done():
    runs = [(100, 110), (200, 230), (400, 405)]          # device clock
    launches = [1090, 1185, 1380]                         # host clock
    dones = [1115, 1233, 1409]
    off = trace.clock_offset(runs, launches, dones)
    assert off == pytest.approx((1090 - 100 + 1233 - 230) / 2)
    for (a, b), l, d in zip(runs, launches, dones):
        assert l <= a + off and b + off <= d
    with pytest.raises(ValueError, match="pair"):
        trace.clock_offset(runs, launches[:2], dones)
    with pytest.raises(ValueError, match="disagree"):
        trace.clock_offset(runs, [1090, 1185, 1410], dones)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({0: [ev("x", 0, 1)]}, [ev("decode", 0, 1)])


def test_recorded_v5e_trace():
    devs, host = trace.events(DATA, ("window", "prefill", "decode"))
    assert list(devs) == [0] and devs[0]
    names = {e[0] for e in host}
    assert names == {"window", "prefill", "decode"}
    red = trace.reduce(devs, host)
    assert 0 < red["busy_s"] < red["window_s"]
    idle = sum(s for _, s in trace.idle_gaps(devs[0], host, red["w0"], red["w1"]))
    assert red["busy_s"] + idle == pytest.approx(red["window_s"], rel=1e-9)
    gates = trace.matching(devs[0], "topk_gate", 0, float("inf"))
    assert len(gates) == 6
    assert sum("f32[16,60]" in e[0] for e in gates) == 3
    assert sum("f32[1,128]" in e[0] for e in gates) == 3
    assert {n for n, _ in red["idle_gaps"]} <= {"prefill", "decode", "none"}


def test_recorded_device_work_lands_in_the_host_span_that_issued_it():
    """On the device's own clock the first gate runs before its prefill span
    opened; on the host's clock every op lies inside the span that launched
    it, and each host sleep between ``prefill`` and ``decode`` (outside any
    annotation) is an idle gap named ``none``, not ``prefill``."""
    devs, host = trace.events(DATA, ("window", "prefill", "decode"))
    spans = {n: [(a, b) for m, a, b, _ in host if m == n] for n in ("prefill", "decode")}

    def inside(e, name):
        return any(a <= e[1] and e[2] <= b for a, b in spans[name])

    for e in devs[0]:
        assert inside(e, "decode" if "f32[1,128]" in e[0] else "prefill"), e
    after_prefill = [(b, a2) for (_, b), (a2, _) in zip(spans["prefill"], spans["decode"])]
    gaps = []
    t = None
    for a, b in trace.union(devs[0], host[0][1], host[0][2]):
        if t is not None and any(p <= (t + a) / 2 <= q for p, q in after_prefill):
            gaps.append((t, a))
        t = b
    assert len(gaps) == 3
    names = trace.idle_gaps(devs[0], host, host[0][1], host[0][2])
    for t, a in gaps:
        assert (("none", pytest.approx((a - t) * 1e-9)) in
                [(n, pytest.approx(s)) for n, s in names])


def test_topk_gate_roofline_reads_the_recorded_gates():
    devs, host = trace.events(DATA, ("window", "prefill", "decode"))
    ctx = SimpleNamespace(ops=devs[0], trace_window=(0, float("inf")),
                          shapes=SimpleNamespace(top_k=4),
                          peak=counts.peaks("TPU v5 lite"))
    share = harness.load_metric("topk_gate_roofline")(ctx)
    gates = trace.matching(devs[0], "topk_gate", 0, float("inf"))
    least = sum(counts.roofline_s(*counts.topk_gate(t, e, 4), ctx.peak)
                for t, e in ((16, 60),) * 3 + ((1, 128),) * 3)
    spent = sum(b - a for _, a, b, _ in gates) * 1e-9
    assert 0 < share < 100 and share == pytest.approx(100 * least / spent)


def test_unpaired_runs_leave_the_gaps_unnamed(monkeypatch):
    monkeypatch.setattr(trace, "DONE", "no such event")
    devs, host = trace.events(DATA, ("window", "prefill", "decode"))
    red = trace.reduce(devs, host)
    assert 0 < red["busy_s"] < red["window_s"]
    assert {n for n, _ in red["idle_gaps"]} <= {trace.UNALIGNED, "none"}
