"""BENCHMARK.json holds to its shape, every name in it finds its file, and a
metric, configuration or traffic mix can be added as a new file found by name
(here under a temporary directory searched first; nothing is written into
the checkout)."""
import json
import os
import re
from types import SimpleNamespace

import pytest

import benchpath  # noqa: F401

from bench import harness

B = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_entries_have_their_files_and_keys():
    assert B["command"] == ["python3", "bench/run.py"] and B["paths"] == ["bench"]
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(harness.CHECKOUT, c["file"]))
        assert harness.find("configs", c["name"]) == os.path.join(harness.CHECKOUT, c["file"])
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        harness.load_json("cells", w["name"])
        harness.load_json("traffic", w["traffic"])
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    for m in B["per_layer"]:
        assert callable(harness.load_metric(m["name"]))
        assert m["moves"] in {e["name"] for e in B["end_to_end"]}
    assert {m["name"] for m in B["end_to_end"]} == {
        "output_tok_s", "itl_p95_ms", "ttft_p50_ms", "hbm_peak_gb", "setup_s"}


def test_cell_limits_sit_between_readings():
    for w in B["workloads"]:
        limits = harness.load_json("cells", w["name"])["limits"]
        assert limits and all(v > 0 for v in limits.values())


def test_an_added_metric_file_is_found_and_read(tmp_path):
    before = sorted(os.listdir(os.path.join(harness.BENCH, "metrics")))
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "tokens_twice.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.tokens if ctx.tokens else None\n")
    roots = [str(tmp_path), harness.BENCH]
    ctx = SimpleNamespace(tokens=21, counters={"device_dispatches": 42, "hits": 3,
                                               "misses": 1, "bytes_uploaded": 0})
    assert harness.load_metric("tokens_twice", roots)(ctx) == 42.0
    assert harness.load_metric("dispatches_per_token", roots)(ctx) == 2.0
    assert harness.load_metric("miss_rate", roots)(ctx) == 25.0
    assert harness.load_metric("tokens_twice", roots)(SimpleNamespace(tokens=0)) is None
    with pytest.raises(FileNotFoundError):
        harness.load_metric("tokens_twice")
    assert sorted(os.listdir(os.path.join(harness.BENCH, "metrics"))) == before


def test_an_added_config_and_mix_are_found(tmp_path):
    for kind, name, body in (("configs", "extra-model", {"hidden_size": 8}),
                             ("traffic", "extra-mix", {"prompt_len": 8, "output_len": 2})):
        (tmp_path / kind).mkdir()
        (tmp_path / kind / f"{name}.json").write_text(json.dumps(body))
        assert harness.load_json(kind, name, [str(tmp_path), harness.BENCH]) == body
    assert harness.load_json("traffic", "chat", [str(tmp_path), harness.BENCH])["prompt_len"] == 512
