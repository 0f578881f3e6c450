"""Per-arch smoke tests (deliverable f): every assigned architecture's reduced
config runs a real forward/train step on CPU — correct shapes, no NaNs — plus
prefill+decode consistency for one arch per family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import params_for
from repro.configs import ALL_ARCHS
from repro.models import decode_model, lm_loss, prefill_model
from repro.models.transformer import Runtime


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_smoke_train_step(arch, rng):
    cfg, params = params_for(arch)
    rt = Runtime()
    s = 24
    s_tok = s - (cfg.frontend_len if cfg.frontend else 0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, s_tok)), jnp.int32)
    fe = None
    if cfg.frontend:
        fe = jnp.asarray(rng.standard_normal((2, cfg.frontend_len, cfg.frontend_dim)),
                         jnp.float32)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: lm_loss(cfg, p, tokens, tokens, rt, fe), has_aux=True
    )(params)
    assert np.isfinite(float(loss))
    assert float(loss) > 0
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_smoke_decode_step(arch, rng):
    cfg, params = params_for(arch)
    rt = Runtime(cache_len=32)
    s_tok = 16 - (cfg.frontend_len if cfg.frontend else 0)
    if cfg.frontend:
        s_tok = 8
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, s_tok)), jnp.int32)
    fe = None
    if cfg.frontend:
        fe = jnp.asarray(rng.standard_normal((2, cfg.frontend_len, cfg.frontend_dim)),
                         jnp.float32)
    logits, state = prefill_model(cfg, params, tokens, rt, fe)
    assert logits.shape == (2, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    cur = s_tok + (cfg.frontend_len if cfg.frontend else 0)
    lg2, state, _ = decode_model(cfg, params, jnp.argmax(logits, -1).astype(jnp.int32),
                                 state, jnp.int32(cur), rt)
    assert lg2.shape == (2, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(lg2, np.float32)))


@pytest.mark.parametrize("arch", ["starcoder2-3b", "qwen2-moe-a2.7b",
                                  "recurrentgemma-2b", "xlstm-350m"])
def test_decode_matches_teacher_forcing(arch, rng):
    """Greedy decode logits must match the training forward at the same
    positions (KV-cache / recurrent-state correctness end to end)."""
    cfg, params = params_for(arch)
    rt = Runtime(cache_len=24)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 12)), jnp.int32)
    from repro.models import forward_train, lm_logits

    h, _ = forward_train(cfg, params, tokens, rt)
    logits_tf = lm_logits(cfg, params, h)              # [1, 12, V]
    # bf16 params + different-but-equivalent dispatch paths (train: sorted,
    # decode: gathered) round differently; compare within bf16 noise and on
    # the greedy decision
    tol = dict(atol=6e-2, rtol=6e-2)
    logits_pre, state = prefill_model(cfg, params, tokens[:, :8], rt)
    np.testing.assert_allclose(
        np.asarray(logits_pre, np.float32), np.asarray(logits_tf[:, 7], np.float32),
        **tol,
    )
    assert int(np.argmax(logits_pre)) == int(np.argmax(logits_tf[:, 7]))
    for t in range(8, 12):
        lg, state, _ = decode_model(cfg, params, tokens[:, t], state,
                                    jnp.int32(t), rt)
        np.testing.assert_allclose(
            np.asarray(lg, np.float32), np.asarray(logits_tf[:, t], np.float32),
            **tol,
        )
        assert int(np.argmax(lg)) == int(np.argmax(logits_tf[:, t]))


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "qwen36-35b-a3b"])
def test_init_experts_on_host_matches_device_init(arch):
    """Experts drawn layer by layer into host memory are the device init's
    values, as numpy arrays in the model dtype; nothing else moves."""
    from repro.models import init_params

    cfg, dev = params_for(arch)
    host = init_params(cfg, jax.random.PRNGKey(0), experts_on_host=True)
    leaves_d, tree_d = jax.tree.flatten(dev)
    leaves_h, tree_h = jax.tree.flatten(host)
    assert tree_d == tree_h
    for a, b in zip(leaves_d, leaves_h):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for w in host["segments"][0][0]["moe"]["experts"].values():
        assert isinstance(w, np.ndarray) and w.dtype == jnp.dtype(cfg.dtype)
    assert isinstance(host["embed"], jax.Array)


def test_cut_depth_keeps_published_widths():
    from repro.config import get_config
    from repro.configs import cut_depth

    full = get_config("qwen36-35b-a3b")
    cut = cut_depth(full, 4)
    assert cut.num_layers == 4 and cut.name == "qwen36-35b-a3b-4L"
    assert (cut.d_model, cut.vocab_size, cut.attention, cut.moe, cut.dtype) == (
        full.d_model, full.vocab_size, full.attention, full.moe, full.dtype)
    assert cut_depth(full, full.num_layers) is full
    # multi-kind units are kept whole
    rg = get_config("recurrentgemma-2b")
    assert cut_depth(rg, 4).num_layers == 3
    with pytest.raises(ValueError):
        cut_depth(full, 0)
    with pytest.raises(ValueError):
        cut_depth(rg, 2)            # no whole 3-layer unit fits
