"""The ``expert_rows_per_pick`` reader on made-up span lists in the
``Tracer``'s record form ``(ph, name, track, lane, t0, dur, unit, args)``."""
from types import SimpleNamespace

import pytest

import benchpath  # noqa: F401

from bench import harness


def telemetry(unit, **args):
    return ("X", "telemetry", "pull", None, 0.1 * unit, 0.0001, unit, args or None)


def read(spans):
    return harness.load_metric("expert_rows_per_pick")(SimpleNamespace(spans=spans))


def test_expert_rows_per_pick_is_the_share_over_chunk_units():
    spans = [telemetry(1, picks=64, rows=40), telemetry(2),     # a decode unit
             telemetry(3, picks=64, rows=44)]
    assert read(spans) == pytest.approx((40 + 44) / 128)


def test_expert_rows_per_pick_is_none_without_the_count():
    # the parent's spans: telemetry with no args, or args of other kinds
    assert read([telemetry(1), telemetry(2, kind="chunk")]) is None
    assert read([]) is None
