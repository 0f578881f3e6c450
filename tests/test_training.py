"""Trainer: optimizer math, microbatch equivalence, loss goes down, compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import params_for
from jax import shard_map
from repro.config import RunConfig
from repro.data import SyntheticSpec, batch_at_step
from repro.models.transformer import Runtime
from repro.training import init_train_state, make_train_step
from repro.training.optimizer import adamw_init, adamw_update, global_norm, lr_at


def test_lr_schedule():
    run = RunConfig(learning_rate=1e-3, warmup_steps=10, total_steps=100)
    assert float(lr_at(run, jnp.int32(0))) == 0.0
    assert abs(float(lr_at(run, jnp.int32(10))) - 1e-3) < 1e-9
    assert float(lr_at(run, jnp.int32(100))) < 2e-4      # cosine floor 10%
    assert float(lr_at(run, jnp.int32(50))) < 1e-3


def test_adamw_step_moves_params():
    run = RunConfig(learning_rate=1e-2, warmup_steps=0)
    params = {"w": jnp.ones((4, 4))}
    opt = adamw_init(params)
    grads = {"w": jnp.ones((4, 4))}
    new_p, new_opt, m = adamw_update(params, grads, opt, run)
    assert float(new_opt["step"]) == 1
    assert not np.allclose(np.asarray(new_p["w"]), 1.0)
    assert float(m["grad_norm"]) == pytest.approx(4.0)


def test_grad_clip_applied():
    run = RunConfig(learning_rate=1e-2, grad_clip=0.1, warmup_steps=0,
                    weight_decay=0.0)
    params = {"w": jnp.zeros((2,))}
    opt = adamw_init(params)
    big = {"w": jnp.full((2,), 100.0)}
    small = {"w": jnp.full((2,), 100.0) * 0.1 / global_norm(big)}
    p1, o1, _ = adamw_update(params, big, opt, run)
    p2, o2, _ = adamw_update(params, small, adamw_init(params), run)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]), atol=1e-6)


def test_microbatch_equivalence(rng):
    """num_micro=1 and num_micro=2 produce (nearly) the same updated params."""
    cfg, params = params_for("starcoder2-3b")
    rt = Runtime()
    run = RunConfig(learning_rate=1e-3, warmup_steps=0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
    s1 = init_train_state(cfg, params)
    s2 = init_train_state(cfg, params)
    f1 = jax.jit(make_train_step(cfg, rt, run, num_micro=1))
    f2 = jax.jit(make_train_step(cfg, rt, run, num_micro=2))
    s1, m1 = f1(s1, tokens, tokens)
    s2, m2 = f2(s2, tokens, tokens)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    for a, b in zip(jax.tree.leaves(s1["params"]), jax.tree.leaves(s2["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-3)


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "xlstm-350m"])
def test_loss_decreases(arch):
    cfg, params = params_for(arch)
    rt = Runtime()
    run = RunConfig(learning_rate=3e-3, warmup_steps=1)
    spec = SyntheticSpec(vocab_size=cfg.vocab_size, seq_len=24, global_batch=4,
                         kind="topic", num_topics=2, topic_len=8)
    state = init_train_state(cfg, params)
    step_fn = jax.jit(make_train_step(cfg, rt, run))
    losses = []
    for i in range(5):
        t, l = batch_at_step(spec, i)
        state, m = step_fn(state, jnp.asarray(t), jnp.asarray(l))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_int8_ef_compression_unbiased():
    """Quantize + error feedback: averaged over steps, the compressed gradient
    converges to the true gradient (EF eats the bias)."""
    from repro.training.compression import compressed_psum_pod, ef_init

    g_true = {"w": jnp.asarray(np.linspace(-1, 1, 64).reshape(8, 8), jnp.float32)}
    ef = jax.tree.map(lambda x: x[None].astype(jnp.bfloat16),
                      jax.tree.map(jnp.zeros_like, g_true))
    mesh = jax.make_mesh((1,), ("pod",))
    from jax.sharding import PartitionSpec as P

    def step(ef):
        f = shard_map(
            lambda e: compressed_psum_pod(g_true, e, axis="pod", pod_count=1),
            mesh=mesh, in_specs=(P(),), out_specs=(P(), P()), check_vma=False,
        )
        return f(ef)

    acc = jnp.zeros((8, 8))
    n = 20
    for _ in range(n):
        out, ef = step(ef)
        acc = acc + out["w"]
    np.testing.assert_allclose(np.asarray(acc / n), np.asarray(g_true["w"]),
                               atol=5e-3)
