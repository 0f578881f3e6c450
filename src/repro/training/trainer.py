"""Training step + loop: microbatched grad accumulation, AdamW, optional
cross-pod int8 gradient compression, checkpoint/restart hooks.

``make_train_step`` builds the jit-able step used both for real (reduced-model)
training and for the full-size dry-run lowering. Microbatching reshapes the
global batch [B, S] -> [n_micro, B/n_micro, S] and accumulates f32 grads in a
``lax.scan`` — the standard memory lever that keeps activation residency
bounded at `microbatch` rows regardless of global batch.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax import shard_map
from repro.config.base import ModelConfig, RunConfig
from repro.models.transformer import Runtime, lm_loss
from repro.training.optimizer import adamw_init, adamw_update
from repro.training import compression

TrainState = Dict[str, Any]


def init_train_state(
    cfg: ModelConfig, params: Any, sharding_cfg=None, pod_count: int = 2
) -> TrainState:
    state: TrainState = {"params": params, "opt": adamw_init(params)}
    if sharding_cfg is not None and sharding_cfg.grad_compression == "int8_ef":
        state["ef"] = compression.ef_init(params, pod_count)
    return state


def make_train_step(
    cfg: ModelConfig,
    rt: Runtime,
    run: RunConfig,
    *,
    num_micro: int = 1,
    pod_compression: bool = False,
    pod_count: int = 2,
) -> Callable:
    """Returns train_step(state, tokens, labels, frontend=None) -> (state, metrics)."""

    def loss_fn(params, tokens, labels, frontend):
        loss, aux = lm_loss(cfg, params, tokens, labels, rt, frontend)
        return loss, aux

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, tokens, labels, frontend):
        if num_micro <= 1:
            (loss, aux), grads = grad_fn(params, tokens, labels, frontend)
            return loss, grads
        b = tokens.shape[0]
        mb = b // num_micro
        tk = tokens.reshape(num_micro, mb, *tokens.shape[1:])
        lb = labels.reshape(num_micro, mb, *labels.shape[1:])
        fe = (
            frontend.reshape(num_micro, mb, *frontend.shape[1:])
            if frontend is not None else None
        )

        def micro(carry, xs):
            acc, loss_sum = carry
            if fe is not None:
                t, l, f = xs
            else:
                t, l = xs
                f = None
            (loss, _), grads = grad_fn(params, t, l, f)
            acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / num_micro, acc, grads
            )
            return (acc, loss_sum + loss / num_micro), None

        acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        xs = (tk, lb, fe) if fe is not None else (tk, lb)
        (grads, loss), _ = jax.lax.scan(micro, (acc0, jnp.zeros(())), xs)
        return loss, grads

    def compute_grads_pod_compressed(params, tokens, labels, frontend, ef):
        """Manual over "pod": each pod computes partial grads on its batch slice
        (data/model axes stay automatic/GSPMD inside), then the pod-axis
        reduction happens as an explicit int8 all-reduce with error feedback."""
        from jax.sharding import PartitionSpec as P

        def inner(params, tokens, labels, frontend, ef):
            loss, grads = compute_grads(params, tokens, labels, frontend)
            grads, new_ef = compression.compressed_psum_pod(
                grads, ef, axis="pod", pod_count=pod_count
            )
            return jax.lax.pmean(loss, "pod"), grads, new_ef

        fe_spec = P() if frontend is None else P("pod")
        fn = shard_map(
            inner,
            mesh=rt.mesh,
            in_specs=(P(), P("pod"), P("pod"), fe_spec, P("pod")),
            out_specs=(P(), P(), P("pod")),
            axis_names={"pod"},
            check_vma=False,
        )
        return fn(params, tokens, labels, frontend, ef)

    def train_step(state, tokens, labels, frontend=None):
        params = state["params"]
        new_state = dict(state)
        if pod_compression and "ef" in state:
            loss, grads, new_state["ef"] = compute_grads_pod_compressed(
                params, tokens, labels, frontend, state["ef"]
            )
        else:
            loss, grads = compute_grads(params, tokens, labels, frontend)
        new_params, new_opt, metrics = adamw_update(params, grads, state["opt"], run)
        new_state["params"] = new_params
        new_state["opt"] = new_opt
        metrics["loss"] = loss
        return new_state, metrics

    return train_step


def train_loop(
    cfg: ModelConfig,
    state: TrainState,
    step_fn: Callable,
    loader,
    run: RunConfig,
    *,
    num_steps: int,
    ckpt_manager=None,
    log: Optional[Callable[[int, Dict], None]] = None,
) -> Tuple[TrainState, Dict[str, float]]:
    last_metrics: Dict[str, float] = {}
    for _ in range(num_steps):
        step, tokens, labels = next(loader)
        state, metrics = step_fn(state, tokens, labels)
        last_metrics = {k: float(v) for k, v in metrics.items()}
        if log is not None and step % run.log_every == 0:
            log(step, last_metrics)
        if ckpt_manager is not None and (step + 1) % run.checkpoint_every == 0:
            ckpt_manager.save(step + 1, state)
    return state, last_metrics
