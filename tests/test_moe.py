"""MoE dispatch implementations must agree with each other (same routing)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from repro.config import MoEConfig
from repro.models import moe as M


def _setup(rng, e=8, k=2, d=16, f=24, shared=0, mlp="swiglu"):
    mcfg = MoEConfig(num_experts=e, top_k=k, expert_d_ff=f,
                     num_shared_experts=shared, shared_d_ff=f if shared else 0,
                     capacity_factor=4.0)   # high cf: no drops -> exact equality
    p = M.init_moe(jax.random.PRNGKey(0), d, mcfg, mlp, jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 6, d)), jnp.float32)
    return mcfg, p, x


@pytest.mark.parametrize("mlp", ["swiglu", "gelu_mlp"])
@pytest.mark.parametrize("shared", [0, 2])
def test_dense_vs_sorted(rng, mlp, shared):
    mcfg, p, x = _setup(rng, shared=shared, mlp=mlp)
    y_dense, _ = M.moe_dense(p, mcfg, x)
    y_sorted, aux = M.moe_sorted(p, mcfg, x.reshape(-1, x.shape[-1]))
    assert float(aux["dropped_frac"]) == 0.0
    np.testing.assert_allclose(
        np.asarray(y_dense).reshape(-1, x.shape[-1]),
        np.asarray(y_sorted), atol=1e-4,
    )


def test_sorted_vs_gathered(rng):
    mcfg, p, x = _setup(rng)
    x2d = x.reshape(-1, x.shape[-1])
    y_sorted, _ = M.moe_sorted(p, mcfg, x2d)
    y_gathered, miss, _ = M.moe_gathered(p, mcfg, x2d)
    assert not bool(miss.any())
    np.testing.assert_allclose(np.asarray(y_sorted), np.asarray(y_gathered),
                               atol=1e-4)


def test_epsum_single_axis_matches_sorted(rng):
    """epsum under a size-1 model axis == sorted (the collective degenerates)."""
    mcfg, p, x = _setup(rng)
    x2d = x.reshape(-1, x.shape[-1])
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    from jax.sharding import PartitionSpec as P

    def fn(p_, x_):
        return M.moe_epsum_local(p_, mcfg, x_, ep_axis="model", ep_size=1)

    f = shard_map(
        fn, mesh=mesh,
        in_specs=({"router": P(None, None),
                   "experts": {kk: P("model", None, None) for kk in p["experts"]}},
                  P("data", None)),
        out_specs=(P("data", None), P()),
        check_vma=False,
    )
    y_ep, _ = jax.jit(f)(p, x2d)
    y_sorted, _ = M.moe_sorted(p, mcfg, x2d)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_sorted), atol=1e-4)


def test_slot_lut_miss_drops_expert(rng):
    """Residency path: a missing expert contributes nothing; resident experts
    keep their exact contribution."""
    mcfg, p, x = _setup(rng, e=4, k=2)
    x2d = x.reshape(-1, x.shape[-1])
    logits = M.router_logits(p, x2d)
    ids, weights, _ = M.topk_route(logits, mcfg)
    # slots hold experts 0 and 1 only; 2,3 miss
    num_slots = 2
    slot_buffer = {
        n: jnp.concatenate([p["experts"][n][:2],
                            jnp.zeros_like(p["experts"][n][:1])])
        for n in p["experts"]
    }
    lut = jnp.asarray([0, 1, num_slots, num_slots], jnp.int32)
    y, miss = M.moe_apply_routed(p, x2d, ids, weights,
                                 slot_buffer=slot_buffer, lut=lut)
    assert bool(miss.any()) == bool((np.asarray(ids) >= 2).any())
    # reconstruct: full path minus missed contributions
    y_full, _ = M.moe_apply_routed(p, x2d, ids, weights)
    w_missed = np.asarray(weights) * np.asarray(miss)
    # recompute missed expert contributions with numpy
    from repro.core.engine import _np_ffn

    hw = {n: np.asarray(p["experts"][n]) for n in p["experts"]}
    corr = np.zeros_like(np.asarray(y))
    for t, j in zip(*np.nonzero(np.asarray(miss))):
        e = int(np.asarray(ids)[t, j])
        corr[t] += w_missed[t, j] * _np_ffn(
            {n: w[e] for n, w in hw.items()}, np.asarray(x2d)[t]
        )
    np.testing.assert_allclose(np.asarray(y) + corr, np.asarray(y_full),
                               atol=2e-3)


def test_capacity_drops_counted(rng):
    mcfg, p, x = _setup(rng)
    mcfg_tight = MoEConfig(num_experts=8, top_k=2, expert_d_ff=24,
                           capacity_factor=0.25)
    _, aux = M.moe_sorted(p, mcfg_tight, x.reshape(-1, x.shape[-1]))
    assert float(aux["dropped_frac"]) > 0.0


def test_aux_losses_finite(rng):
    mcfg, p, x = _setup(rng)
    _, aux = M.moe_dense(p, mcfg, x)
    assert np.isfinite(float(aux["load_balance"]))
    assert np.isfinite(float(aux["router_z"]))
    assert float(aux["load_balance"]) >= 1.0 - 1e-6   # >= 1 by Cauchy-Schwarz
