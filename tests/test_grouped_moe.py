"""The routed experts of the engine paths run grouped by slot
(``moe_apply_routed`` -> ``ops.moe_slot_ffn``): against a per-pick gather of
the same slot planes and LUT (the form the fused steps ran before), in f32
and bf16, for one token up to a 64-token chunk, with forced misses, with
every token on one slot, and with every pick missed. The grouped form sums
in another order, so outputs agree to rounding, not bitwise; the routing and
miss telemetry it returns is the same bit for bit."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import params_for
from repro.core.engine import build_fused_prefill_step
from repro.kernels import moe_gmm, ops
from repro.models import init_params
from repro.models import moe as moe_mod
from repro.models import transformer as tfm
from repro.models.transformer import Runtime

L, E, S, D, F, K = 3, 8, 5, 16, 24, 2      # layers, experts, slots, widths, top-k


def _gather_ffn(x2d, gidx, planes, layer):
    """Each pick's expert FFN output [T, k, D] from a gathered [T, k, D, F]
    copy of its slot rows, the miss slot's zero rows included."""
    w = {n: (v[layer] if v.ndim == 4 else v)[gidx] for n, v in planes.items()}
    h = jax.nn.silu(jnp.einsum("td,tkdf->tkf", x2d, w["w_gate"])) * jnp.einsum(
        "td,tkdf->tkf", x2d, w["w_up"])
    return jnp.einsum("tkf,tkfd->tkd", h, w["w_down"])


def _setup(rng, dtype, t, resident=S):
    """Stacked planes [L, S+1, ...] with a zero miss slot, a LUT holding the
    first ``resident`` experts of each layer (the rest miss), shared-expert
    params, tokens and routing."""
    planes = {
        n: jnp.asarray(0.3 * rng.standard_normal((L, S + 1) + shape), dtype)
        .at[:, S].set(0)
        for n, shape in (("w_gate", (D, F)), ("w_up", (D, F)), ("w_down", (F, D)))
    }
    lut = np.full((L, E), S, np.int32)
    for layer in range(L):
        lut[layer, rng.permutation(E)[:resident]] = np.arange(resident)
    p = {"shared": {n: jnp.asarray(0.3 * rng.standard_normal(shape), dtype)
                    for n, shape in (("w_gate", (D, 8)), ("w_up", (D, 8)),
                                     ("w_down", (8, D)))},
         "shared_gate": jnp.asarray(rng.standard_normal((D, 1)), dtype)}
    x = jnp.asarray(rng.standard_normal((t, D)), dtype)
    ids = jnp.asarray(np.stack([rng.permutation(E)[:K] for _ in range(t)]),
                      jnp.int32)
    weights = jnp.asarray(rng.random((t, K)), jnp.float32)
    return p, planes, jnp.asarray(lut), x, ids, weights


@pytest.fixture
def grouped():
    """``moe_apply_routed`` over slot planes, jitted."""
    return jax.jit(lambda p, x, ids, weights, planes, lut, layer:
                   moe_mod.moe_apply_routed(p, x, ids, weights,
                                            slot_buffer=planes,
                                            lut=lut[layer], layer=layer))


@jax.jit
def _gathered(p, x, ids, weights, planes, lut, layer):
    num_slots = planes["w_up"].shape[-3] - 1
    slots = lut[layer][ids]
    miss = slots >= num_slots
    outs = _gather_ffn(x, jnp.where(miss, num_slots, slots), planes, layer)
    y = jnp.einsum("tkd,tk->td", outs.astype(jnp.float32),
                   weights * (~miss).astype(jnp.float32)).astype(x.dtype)
    return y + moe_mod._shared_ffn(p, x), miss


def _close(a, b, dtype):
    # f32: rounding of a reordered sum; bf16: a unit in the last place of
    # each bf16 GEMM output, carried through the combine
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 else dict(
        rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t", [1, 4, 16, 64])
def test_grouped_matches_gather(rng, grouped, dtype, t):
    """Random routing over 8 experts of which 5 are resident per layer (so
    picks miss), read at the middle layer of the stack and through that
    layer's own plane, as the per-layer walk passes it."""
    p, planes, lut, x, ids, weights = _setup(rng, dtype, t)
    layer = jnp.int32(1)
    y, miss = grouped(p, x, ids, weights, planes, lut, layer)
    y_ref, miss_ref = _gathered(p, x, ids, weights, planes, lut, layer)
    np.testing.assert_array_equal(miss, miss_ref)
    assert int(miss.sum()) > 0 or t == 1
    _close(y, y_ref, dtype)
    one = {n: v[1] for n, v in planes.items()}
    y_one, miss_one = grouped(p, x, ids, weights, one, lut, layer)
    np.testing.assert_array_equal(y_one, y)
    np.testing.assert_array_equal(miss_one, miss)


@pytest.mark.parametrize("t", [1, 16])
def test_grouped_kernel_matches_gather_at_default_blocks(monkeypatch, t):
    """The Pallas kernel (interpreted) in the form the engine runs it on the
    TPU: bf16 stacked planes flattened with the ``layer * (S+1)`` offset, 60
    slots that the T*k picks leave mostly empty, default blocks (512-wide
    weight tiles over K = 1024 of the gate and up matrices and N = 1024 of the
    down matrix), and a row tile of T*k rows (4 at T = 1, not a multiple of
    8). On the CPU the engine takes ``ragged_dot`` instead, so this is the
    kernel path's own check against the gather."""
    layers, experts, slots, d, f, k = 2, 64, 60, 1024, 256, 4
    key = jax.random.PRNGKey(t)

    def normal(i, shape, scale):
        return (scale * jax.random.normal(jax.random.fold_in(key, i), shape)
                ).astype(jnp.bfloat16)

    planes = {
        n: normal(i, (layers, slots + 1) + shape, shape[0] ** -0.5).at[:, slots].set(0)
        for i, (n, shape) in enumerate(
            (("w_gate", (d, f)), ("w_up", (d, f)), ("w_down", (f, d))))
    }
    # experts 60-63 of each layer miss
    lut = jnp.stack([jax.random.permutation(jax.random.fold_in(key, 10 + i),
                                            experts) for i in range(layers)])
    lut = jnp.where(lut < slots, lut, slots).astype(jnp.int32)
    x = normal(20, (t, d), 1.0)
    ids = jnp.argsort(jax.random.uniform(jax.random.fold_in(key, 21),
                                         (t, experts)), axis=1)[:, :k]
    weights = jax.random.uniform(jax.random.fold_in(key, 22), (t, k))
    layer = jnp.int32(1)

    monkeypatch.setattr(ops, "moe_slot_ffn",
                        functools.partial(moe_gmm.moe_slot_ffn, interpret=True))
    y, miss = jax.jit(functools.partial(moe_mod.moe_apply_routed, {}))(
        x, ids, weights, slot_buffer=planes, lut=lut[1], layer=layer)

    num_slots = slots
    gidx = jnp.where(miss, num_slots, lut[1][ids])
    outs = _gather_ffn(x, gidx, planes, layer)
    y_ref = jnp.einsum("tkd,tk->td", outs.astype(jnp.float32),
                       weights * (~miss).astype(jnp.float32)).astype(x.dtype)
    np.testing.assert_array_equal(miss, lut[1][ids] >= slots)
    _close(y, y_ref, jnp.bfloat16)


def test_routed_experts_count_the_planes_they_read(rng):
    """``aux["moe_reads"]``: one per slot group the grouped path runs (the
    distinct resident slots among the picks), one per pick gathered from the
    expert store."""
    p, planes, lut, x, ids, weights = _setup(rng, jnp.float32, 16)
    aux = {}
    moe_mod.moe_apply_routed(p, x, ids, weights, slot_buffer=planes,
                             lut=lut[1], layer=jnp.int32(1), aux=aux)
    slots = np.asarray(lut[1])[np.asarray(ids)]
    assert 0 < int(aux["moe_reads"]) == len(set(slots[slots < S].tolist())) < 16 * K
    p["experts"] = {n: jnp.asarray(rng.standard_normal((E,) + v.shape[2:]),
                                   jnp.float32) for n, v in planes.items()}
    aux = {}
    moe_mod.moe_apply_routed(p, x, ids, weights, aux=aux)
    assert int(aux["moe_reads"]) == 16 * K


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_token_steps_read_each_picked_slot_once(rng, dtype):
    """A one-token step (decode) runs grouped like a chunk: one group, and
    one plane read, per resident pick (its k picks are distinct experts);
    the output agrees with the gather."""
    p, planes, lut, x, ids, weights = _setup(rng, dtype, 1)

    @jax.jit
    def step(p, x, ids, weights, planes, lut):
        aux = {}
        y, miss = moe_mod.moe_apply_routed(
            p, x, ids, weights, slot_buffer=planes, lut=lut[1],
            layer=jnp.int32(1), aux=aux)
        return y, miss, aux["moe_reads"]

    y, miss, reads = step(p, x, ids, weights, planes, lut)
    y_ref, miss_ref = _gathered(p, x, ids, weights, planes, lut, jnp.int32(1))
    np.testing.assert_array_equal(miss, miss_ref)
    _close(y, y_ref, dtype)
    assert int(reads) == K - int(miss.sum())


def test_grouped_one_slot_takes_every_token(rng, grouped):
    """Every token's first pick on one resident expert, its second missed:
    one group of T rows."""
    p, planes, lut, x, _, weights = _setup(rng, jnp.float32, 16)
    layer = 2
    hit = int(np.flatnonzero(np.asarray(lut[layer]) < S)[0])
    gone = int(np.flatnonzero(np.asarray(lut[layer]) == S)[0])
    ids = jnp.tile(jnp.asarray([[hit, gone]], jnp.int32), (16, 1))
    y, miss = grouped(p, x, ids, weights, planes, lut, jnp.int32(layer))
    y_ref, miss_ref = _gathered(p, x, ids, weights, planes, lut, jnp.int32(layer))
    np.testing.assert_array_equal(miss, miss_ref)
    assert not miss[:, 0].any() and miss[:, 1].all()
    _close(y, y_ref, jnp.float32)


def test_grouped_every_pick_missed_leaves_the_shared_expert(rng, grouped):
    """No resident expert: no group, and ``y`` is the shared expert alone."""
    p, planes, lut, x, ids, weights = _setup(rng, jnp.float32, 16, resident=0)
    y, miss = grouped(p, x, ids, weights, planes, lut, jnp.int32(0))
    assert miss.all()
    np.testing.assert_array_equal(y, moe_mod._shared_ffn(p, x))


def test_fused_chunk_telemetry_matches_the_gather(rng, monkeypatch):
    """A fused chunk over 3 MoE layers with forced misses: the grouped path's
    ``route_ids`` and ``route_miss`` equal the gather's bit for bit in every
    layer, and the first layer's ``route_weights``, ``route_h`` and
    ``route_x`` too (later layers read outputs summed in another order)."""
    cfg, _ = params_for("qwen2-moe-a2.7b")
    cfg = dataclasses.replace(
        cfg, dtype="float32", segments=((("attn_moe",), 3),)
    )
    params = init_params(cfg, jax.random.PRNGKey(3))
    rt = Runtime(cache_len=32)
    n_exp, n_slots = cfg.moe.storage_experts, 4
    experts = params["segments"][0][0]["moe"]["experts"]
    lut = np.full((3, n_exp), n_slots, np.int32)
    rows = np.zeros((3, n_slots + 1), np.int32)
    for layer in range(3):
        resident = rng.permutation(cfg.moe.num_experts)[:n_slots]
        lut[layer, resident] = np.arange(n_slots)
        rows[layer, :n_slots] = resident
    zero_row = np.arange(n_slots + 1) == n_slots
    planes = {
        n: jnp.where(zero_row[None, :, None, None], 0.0,
                     jnp.take_along_axis(w, rows[:, :, None, None], axis=1))
        for n, w in experts.items()
    }
    residency = ({"slots": planes, "lut": jnp.asarray(lut)},)
    state = tfm.zero_state(cfg, 2, rt.cache_len)
    prompt = jnp.asarray(rng.integers(0, 200, (2, 8)), jnp.int32)

    def run():
        chunk = build_fused_prefill_step(
            cfg, rt, with_demand=False, donate_state=False
        )
        return chunk(params, None, prompt, state, jnp.int32(0), residency)[2]

    aux = run()
    monkeypatch.setattr(moe_mod, "_grouped_slot_ffn",
                        lambda *a: (_gather_ffn(*a), jnp.int32(0)))
    aux_ref = run()
    miss = np.asarray(aux["route_miss/seg0"])
    assert 0 < miss.sum() < miss.size
    for key in ("route_ids/seg0", "route_miss/seg0"):
        np.testing.assert_array_equal(aux[key], aux_ref[key], err_msg=key)
    for key in ("route_weights/seg0", "route_h/seg0", "route_x/seg0"):
        np.testing.assert_array_equal(aux[key][0], aux_ref[key][0], err_msg=key)
        np.testing.assert_allclose(aux[key], aux_ref[key], rtol=1e-5,
                                   atol=1e-5, err_msg=key)
