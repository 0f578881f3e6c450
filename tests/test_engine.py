"""RotaryEngine: the exactness property (host miss-correction makes every
policy produce IDENTICAL greedy tokens) + accounting sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import params_for
from repro.config import ResidencyConfig
from repro.core import CostModel, RotaryEngine
from repro.models.transformer import Runtime


def _engine(arch, mode, slots, dtype=None, **kw):
    cfg, params = params_for(arch)
    if dtype is not None:
        import dataclasses

        import jax.numpy as jnp
        from repro.models import init_params

        cfg = dataclasses.replace(cfg, dtype=dtype)
        params = init_params(cfg, jax.random.PRNGKey(0))
    res = ResidencyConfig(mode=mode, num_slots=slots, prefetch_margin=2, **kw)
    return cfg, RotaryEngine(cfg, params, res, rt=Runtime(cache_len=64), batch=2)


@pytest.mark.parametrize("arch", ["qwen36-35b-a3b", "qwen2-moe-a2.7b"])
def test_all_policies_exact(arch, rng):
    """Greedy decode tokens are identical under full / rotary / lru / static —
    the engine's miss correction is exact, residency changes only WHERE
    compute happens (paper §4: behaviour preserved, residency managed).

    Exactness requires host dtype == device compute dtype (f32 here): under
    bf16 device compute the f32 host correction is *more* accurate than the
    device path it replaces, so near-tie argmax tokens may differ — that skew
    is bounded by bf16 epsilon and covered by test_int8_residency_close_logits.
    """
    prompt = rng.integers(0, 200, (2, 10)).astype(np.int32)
    outs = {}
    for mode, slots in [("full", 0), ("rotary", 5), ("lru", 5), ("static", 5)]:
        cfg, eng = _engine(arch, mode, slots, dtype="float32")
        outs[mode] = eng.generate(prompt, 8)
    for mode in ("rotary", "lru", "static"):
        np.testing.assert_array_equal(outs["full"], outs[mode])


def test_rotary_prefetch_beats_lru_on_bytes(rng):
    """Rotary moves bytes off the critical path: stalls modeled lower than
    LRU's blocking loads under a recurring workload."""
    prompt = rng.integers(0, 200, (2, 12)).astype(np.int32)
    _, rot = _engine("qwen36-35b-a3b", "rotary", 5)
    rot.generate(prompt, 12)
    _, lru = _engine("qwen36-35b-a3b", "lru", 5)
    lru.generate(prompt, 12)
    # LRU stalls on every miss-load; rotary misses go to host & prefetch hides DMA
    assert rot.stats.hit_rate >= 0.3
    assert lru.stats.stall_s > 0.0


def test_residency_restricts_device_params():
    """The device layer params never contain the expert store (the warehouse
    stays in host memory, in the model dtype); full residency holds every
    expert in its slot stores instead."""
    cfg, eng = _engine("qwen36-35b-a3b", "rotary", 5)
    for kind, p_l in eng.layers:
        if kind == "attn_moe":
            assert "experts" not in p_l["moe"]
    for hw in eng.host_experts:
        for w in hw.values():
            assert isinstance(w, np.ndarray) and w.dtype == jnp.dtype(cfg.dtype)
    cfg2, eng_full = _engine("qwen36-35b-a3b", "full", 0)
    for kind, p_l in eng_full.layers:
        if kind == "attn_moe":
            assert "experts" not in p_l["moe"]
    for store in eng_full.manager.stores:
        assert store.num_slots == cfg2.moe.num_experts


def test_stats_accounting(rng):
    cfg, eng = _engine("qwen36-35b-a3b", "rotary", 5)
    prompt = rng.integers(0, 200, (2, 8)).astype(np.int32)
    eng.generate(prompt, 6)
    s = eng.stats
    assert s.steps == 6
    assert s.tokens == 2 * 8 + 2 * 6
    assert s.hits + s.misses == (8 * 2 + 6 * 2) * cfg.moe.top_k * cfg.num_layers
    assert s.bytes_loaded > 0
    assert s.compute_s > 0
    assert s.modeled_step_time() > 0


def test_int8_residency_close_logits(rng):
    """int8 slot quantization (Q4_K_M analog) perturbs logits only mildly on
    the reduced model."""
    cfg, params = params_for("qwen36-35b-a3b")
    prompt = rng.integers(0, 200, (1, 8)).astype(np.int32)
    eng_fp = RotaryEngine(cfg, params, ResidencyConfig(mode="rotary", num_slots=6),
                          rt=Runtime(cache_len=32), batch=1)
    lg_fp = eng_fp.prefill(prompt)
    eng_q = RotaryEngine(cfg, params,
                         ResidencyConfig(mode="rotary", num_slots=6, quantization="int8"),
                         rt=Runtime(cache_len=32), batch=1)
    lg_q = eng_q.prefill(prompt)
    denom = np.abs(lg_fp).max() + 1e-9
    assert np.abs(lg_fp - lg_q).max() / denom < 0.2


def test_modeled_full_scale_throughput():
    """CostModel on the FULL paper arch: decode should land in a plausible
    tok/s range for a v5e chip (sanity of the Table-4 modeling path)."""
    from repro.config import get_config
    from repro.models.params import analytic_params

    cfg = get_config("qwen36-35b-a3b")
    cost = CostModel()
    active_bytes = 2 * analytic_params(cfg, active_only=True)
    t = cost.compute_s(2 * analytic_params(cfg, active_only=True), active_bytes)
    assert 1.0 / t > 50.0          # decode is HBM-bound; far above the paper's 21 tok/s on 8GB-laptop


def test_batch2_matches_two_batch1_runs(rng):
    """Batched greedy decode is row-exact: a batch=2 engine produces the same
    tokens as two independent batch=1 engines over the same prompts (residency
    rotation sees different aggregate demand, but miss correction keeps the
    computed tokens independent of residency)."""
    from conftest import params_for
    import dataclasses
    from repro.models import init_params

    cfg, _ = params_for("qwen2-moe-a2.7b")
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = rng.integers(0, 200, (2, 9)).astype(np.int32)

    def make(batch):
        return RotaryEngine(
            cfg, params, ResidencyConfig(mode="rotary", num_slots=5),
            rt=Runtime(cache_len=64), batch=batch,
        )

    out2 = make(2).generate(prompt, 8)
    out_a = make(1).generate(prompt[:1], 8)
    out_b = make(1).generate(prompt[1:], 8)
    np.testing.assert_array_equal(out2[0], out_a[0])
    np.testing.assert_array_equal(out2[1], out_b[0])


def test_full_matches_rotary_tokens(rng):
    """Full-residency (everything on device, hot path, zero misses) and the
    rotary path (slots + replayed miss correction) agree token-for-token."""
    prompt = rng.integers(0, 200, (2, 8)).astype(np.int32)
    _, eng_full = _engine("qwen2-moe-a2.7b", "full", 0, dtype="float32")
    _, eng_rot = _engine("qwen2-moe-a2.7b", "rotary", 5, dtype="float32")
    np.testing.assert_array_equal(
        eng_full.generate(prompt, 10), eng_rot.generate(prompt, 10)
    )


def test_hot_path_matches_host_routing_baseline(rng):
    """The device-resident hot path reproduces the seed-style engine
    (per-layer blocking host routing) token-for-token, with strictly fewer
    queue-draining device->host pulls."""
    prompt = rng.integers(0, 200, (2, 8)).astype(np.int32)
    cfg, params = params_for("qwen2-moe-a2.7b")
    import dataclasses
    from repro.models import init_params

    cfg = dataclasses.replace(cfg, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))

    def make(host_routing):
        return RotaryEngine(
            cfg, params, ResidencyConfig(mode="rotary", num_slots=5),
            rt=Runtime(cache_len=64), batch=2, host_routing=host_routing,
        )

    eng_hot, eng_base = make(False), make(True)
    out_hot = eng_hot.generate(prompt, 8)
    out_base = eng_base.generate(prompt, 8)
    np.testing.assert_array_equal(out_hot, out_base)
    assert eng_hot._hot_decode and not eng_base._hot_decode
    # mechanism parity: same number of routed assignments accounted, and every
    # counted miss was host-corrected in both engines
    assert (eng_hot.stats.hits + eng_hot.stats.misses
            == eng_base.stats.hits + eng_base.stats.misses)
    assert sum(l.host_computed for l in eng_hot.stats.layers.values()) \
        == eng_hot.stats.misses
    assert sum(l.host_computed for l in eng_base.stats.layers.values()) \
        == eng_base.stats.misses


def test_hot_decode_one_sync_pull_per_token(rng):
    """Acceptance: on the miss-free path (full residency) the decode step
    issues exactly ONE queue-draining device->host transfer per token."""
    prompt = rng.integers(0, 200, (2, 8)).astype(np.int32)
    _, eng = _engine("qwen2-moe-a2.7b", "full", 0)
    logits = eng.prefill(prompt)
    pulls_after_prefill = eng.stats.sync_pulls
    steps = 6
    eng.decode(logits, steps)
    assert eng.stats.sync_pulls - pulls_after_prefill == steps
    assert eng.stats.misses == 0
