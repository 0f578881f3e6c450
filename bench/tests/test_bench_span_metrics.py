"""The readers of the engine's spans (``decode_host_ms``, ``chunk_host_ms``,
``rotation_ms_per_token``) on made-up span lists in the ``Tracer``'s record
form ``(ph, name, track, lane, t0, dur, unit, args)``."""
from types import SimpleNamespace

import pytest

import benchpath  # noqa: F401

from bench import harness


def rec(name, t0, dur, unit, track="launch", **args):
    return ("X", name, track, None, float(t0), float(dur), unit, args or None)


def unit_spans(unit, kind, t0, step, pulls, rotation=0.0):
    """One unit: its ``unit`` instant, pulls, a rotation, then the enclosing
    ``step`` (recorded last, as it closes last)."""
    out = [("i", "unit", "launch", None, t0, 0.0, unit, {"kind": kind}),
           rec("launch", t0, 0.001, unit, cur_len=0)]
    at = t0 + 0.001
    for p in pulls:
        out.append(rec("pull", at, p, unit, "pull"))
        at += p
    if rotation:
        out.append(rec("rotation", at, rotation, unit, "rotation", kind="step"))
    out.append(rec("step", t0, step, unit, kind=kind))
    return out


def ctx(spans, tokens=10):
    return SimpleNamespace(spans=spans, tokens=tokens)


def read(metric, c):
    return harness.load_metric(metric)(c)


SPANS = (unit_spans(1, "chunk", 0.0, 0.060, [0.055], rotation=0.002)
         + unit_spans(2, "chunk", 0.1, 0.058, [0.052], rotation=0.001)
         + unit_spans(3, "decode", 0.2, 0.030, [0.025], rotation=0.003)
         + unit_spans(4, "decode", 0.3, 0.034, [0.020, 0.006]))   # a relaunch


def test_decode_host_ms_is_the_step_less_its_pulls():
    assert read("decode_host_ms", ctx(SPANS)) == pytest.approx(
        1e3 * ((0.030 - 0.025) + (0.034 - 0.026)) / 2)


def test_chunk_host_ms_reads_only_chunk_steps():
    assert read("chunk_host_ms", ctx(SPANS)) == pytest.approx(
        1e3 * ((0.060 - 0.055) + (0.058 - 0.052)) / 2)


def test_rotation_ms_per_token_sums_rotation_spans():
    assert read("rotation_ms_per_token", ctx(SPANS, tokens=4)) == pytest.approx(
        1e3 * (0.002 + 0.001 + 0.003) / 4)


@pytest.mark.parametrize("metric", ["decode_host_ms", "chunk_host_ms",
                                    "rotation_ms_per_token"])
def test_span_readers_are_none_without_their_spans(metric):
    # a program without ``step`` spans: launches, pulls and nothing else
    bare = [r for r in SPANS if r[1] not in ("step", "rotation")]
    assert read(metric, ctx(bare)) is None
    assert read(metric, ctx([])) is None


def test_span_readers_keep_decode_and_chunk_apart():
    chunks_only = [r for r in SPANS if r[6] in (1, 2)]
    assert read("decode_host_ms", ctx(chunks_only)) is None
    assert read("chunk_host_ms", ctx(chunks_only)) is not None
    assert read("rotation_ms_per_token", ctx(SPANS, tokens=0)) is None
