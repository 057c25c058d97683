"""The MoE FFN layer on crossbars (`core.moe`, `core.evaluate.evaluate_moe`)
against the float64 reference in `tests/moe_reference.py`, at a small size:
hidden 256, expert width 64, 16 routed experts in 4 groups (2 kept), 4 a
token, one shared expert, 4 held, on 8x8 PCM tiles.

Tolerances: the program solves each tile by float32 Gauss-Seidel; against
the float64 dense MNA oracle its outputs agree to about 5e-6 and its power
to about 1e-6 here. 1e-4 leaves room for that and still fails a bfloat16
solve (about 1e-2) or a dropped or mis-weighted expert (order 1).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import moe_reference as ref
from repro import obs
from repro.core import evaluate as ev
from repro.core.imac import IMACConfig
from repro.core.moe import SHARED, MoESpec, bucket, dispatch, route
from repro.core.neurons import get_neuron

SPEC = MoESpec(d_model=256, d_expert=64, n_experts=16, n_shared=1, top_k=4,
               n_group=4, topk_group=2, scaling=2.5, norm_topk_prob=True)
CFG = IMACConfig(tech="PCM", array_rows=8, array_cols=8)
TOL = 1e-4


def weights(seed=0):
    """float32 weights (the program's) of the router and all 16 + 1 experts."""
    rng = np.random.default_rng(seed)

    def w(*shape, std=0.006):
        return (std * rng.standard_normal(shape)).astype(np.float32)

    d, f = SPEC.d_model, SPEC.d_expert
    experts = {e: (w(d, f), w(d, f), w(f, d)) for e in [SHARED] + list(range(16))}
    return w(16, d), w(16, std=0.01), experts


def tokens(n, seed):
    return np.random.default_rng(seed).standard_normal((n, SPEC.d_model)).astype(np.float32)


def f64(experts):
    return {e: tuple(np.asarray(m, np.float64) for m in ws) for e, ws in experts.items()}


def share(experts, held):
    return {e: experts[e] for e in [SHARED] + list(held)}


@pytest.fixture(scope="module")
def layer_run():
    """The program and the reference on 8 tokens, experts 0-3 held."""
    w_r, b, experts = weights()
    x = tokens(8, 1)
    layer = ev.map_moe(SPEC, CFG, w_r, b, share(experts, range(4)))
    res = ev.evaluate_moe(layer, x)
    want = ref.layer(x, w_r, b, f64(share(experts, range(4))), SPEC, CFG)
    ideal = ref.layer(x, w_r, b, f64(share(experts, range(4))), SPEC, CFG,
                      parasitics=False)
    return res, want, ideal


def rel(a, b, axis=None):
    return np.linalg.norm(np.asarray(a) - b, axis=axis) / np.linalg.norm(b, axis=axis)


def test_entry_matches_reference_outputs_and_routing(layer_run):
    res, (out, (idx, weight), _), (out_ideal, _, _) = layer_run
    np.testing.assert_array_equal(res.topk_idx, idx)
    np.testing.assert_allclose(res.topk_weight, weight, rtol=1e-5)
    assert np.max(rel(res.out, out, axis=1)) < TOL
    assert np.max(rel(res.out_ideal, out_ideal, axis=1)) < 1e-5
    # The wires and drivers move the output by far more than the tolerance.
    assert np.min(res.out_rel_err) > 100 * TOL
    np.testing.assert_allclose(res.out_rel_err, rel(out, out_ideal, axis=1), rtol=1e-2)


def test_entry_matches_reference_pairs_and_power(layer_run):
    res, (_, (idx, weight), powers), _ = layer_run
    assert {(p.token, p.expert) for p in res.pairs} == set(powers)
    assert any(p.expert != SHARED for p in res.pairs)
    for p in res.pairs:
        assert abs(p.device_power - powers[(p.token, p.expert)]) < TOL * powers[(p.token, p.expert)]
        want = 1.0 if p.expert == SHARED else weight[p.token][list(idx[p.token]).index(p.expert)]
        assert p.weight == pytest.approx(want, rel=1e-5)
        # One sense amplifier per tile column of each array: gate and up on
        # 32 x 16 tiles, down on 8 x 32, 8 columns a tile, G+ and G-.
        amps = 2 * 8 * (32 * 16 + 8 * 32)
        assert p.power - p.device_power == pytest.approx(amps * get_neuron("sigmoid").p_amp)
    by_token = np.zeros(len(res.power))
    for p in res.pairs:
        by_token[p.token] += p.power
    np.testing.assert_allclose(res.power, by_token, rtol=1e-12)


def test_shares_add_up_to_the_uncut_layer():
    """Four chips of four experts each: their partial outputs, with the shared
    expert (computed by every chip alike) counted once, are the whole layer."""
    w_r, b, experts = weights()
    x = tokens(4, 2)
    total = np.zeros((4, SPEC.d_model))
    for chip in range(4):
        held = range(4 * chip, 4 * chip + 4)
        res = ev.evaluate_moe(ev.map_moe(SPEC, CFG, w_r, b, share(experts, held)), x)
        total += res.out
        if chip:
            for p in res.pairs:
                if p.expert == SHARED:
                    total[p.token] -= p.detail.z_down
    whole, _, _ = ref.layer(x, w_r, b, f64(experts), SPEC, CFG)
    assert np.max(rel(total, whole, axis=1)) < TOL


def test_router_matches_numpy_copy():
    w_r, b, _ = weights(3)
    x = tokens(64, 4)
    idx, weight = jax.device_get(route(x, w_r, b, SPEC))
    want_idx, want_weight = ref.route(x, w_r, b, SPEC)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(weight, want_weight, rtol=1e-5)
    np.testing.assert_allclose(weight.sum(axis=1), SPEC.scaling, rtol=1e-6)


def test_group_limit_drops_a_top_expert_of_a_weak_group():
    """Expert 0 has the best score, but its group's second best is poor: the
    group-limited router takes the two strongest groups' experts instead."""
    spec = dataclasses.replace(SPEC, d_model=16)
    logits = np.array([4, -4, -4, -4, 1, 1, -4, -4, .9, .8, .7, -4, -4, -4, -4, -4],
                      np.float32)
    eye = np.eye(16, dtype=np.float32)
    idx, _ = route(logits[None], eye, np.zeros(16, np.float32), spec)
    assert sorted(np.asarray(idx[0]).tolist()) == [4, 5, 8, 9]
    plain, _ = route(logits[None], eye, np.zeros(16, np.float32),
                     dataclasses.replace(spec, topk_group=spec.n_group))
    assert sorted(np.asarray(plain[0]).tolist()) == [0, 4, 5, 8]
    want, _ = ref.route(logits[None], eye, np.zeros(16), spec)
    assert sorted(want[0].tolist()) == [4, 5, 8, 9]


def test_deepseek_v3_config_gives_the_published_router():
    from repro.configs import get_config

    spec = MoESpec.from_model_config(get_config("deepseek-v3-671b"))
    assert spec == MoESpec(d_model=7168, d_expert=2048, n_experts=256, n_shared=1, top_k=8,
                           n_group=8, topk_group=4, scaling=2.5, norm_topk_prob=True)
    small = MoESpec.from_model_config(get_config("deepseek-v3-671b").reduced())
    assert (small.n_experts, small.n_group, small.topk_group, small.top_k) == (8, 2, 1, 2)


def test_bucket_and_dispatch():
    assert [bucket(n) for n in range(1, 10)] == [1, 2, 4, 4, 8, 8, 8, 8, 16]
    idx = np.array([[3, 0, 5], [2, 3, 9], [0, 1, 3]])
    weight = np.arange(9, dtype=np.float32).reshape(3, 3)
    out = dispatch(idx, weight, (0, 1, 3, 4))
    assert [(e, t.tolist(), w.tolist()) for e, t, w in out] == [
        (0, [0, 2], [1.0, 6.0]), (1, [2], [7.0]), (3, [0, 1, 2], [0.0, 4.0, 8.0])]


def _routed_counts(x, w_r, b, held):
    idx, _ = ref.route(x, w_r, b, SPEC)
    counts = {e: int(np.sum(idx == e)) for e in held}
    return tuple(counts[e] for e in held)


def test_warm_calls_with_other_routed_counts_trace_nothing():
    w_r, b, experts = weights()
    held = range(4)
    layer = ev.map_moe(SPEC, CFG, w_r, b, share(experts, held))
    warm = [tokens(8, 100 + i) for i in range(6)]
    seen = set()
    for x in warm:
        seen |= {bucket(n) for n in _routed_counts(x, w_r, b, held) if n}
        ev.evaluate_moe(layer, x)
    profiles = {_routed_counts(x, w_r, b, held) for x in warm}
    fresh = []
    for i in range(40):
        x = tokens(8, 200 + i)
        counts = _routed_counts(x, w_r, b, held)
        if counts not in profiles and {bucket(n) for n in counts if n} <= seen:
            profiles.add(counts)
            fresh.append(x)
        if len(fresh) == 2:
            break
    assert len(fresh) == 2
    obs.disable()
    obs.reset()
    obs.enable()
    try:
        for x in fresh:
            ev.evaluate_moe(layer, x)
        traced = [s.name for s in obs.spans() if s.name == "jit_trace"]
        lookups = {s["labels"]["result"]: s["value"] for s in
                   obs.snapshot()["program_cache_lookups_total"]["series"]}
    finally:
        obs.disable()
        obs.reset()
    assert traced == []
    assert set(lookups) == {"hit"}


MLP_RECORDED = [
    (0.75, (0.005951144266873598, 0.0018721240339800715), 9.350478649139404e-07),
    (0.75, (0.0029028994031250477, 0.0009927809005603194), 1.3282988220453262e-07),
]


def test_mlp_group_program_outputs_are_bitwise_as_before():
    """The MLP path shares the program cache and the solve with the MoE layer:
    its outputs stay those recorded before the MoE layer existed."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    params = [(0.3 * jax.random.normal(k1, (16, 8)), jnp.zeros(8)),
              (0.3 * jax.random.normal(k2, (8, 4)), jnp.zeros(4))]
    x = jax.random.uniform(k3, (8, 16))
    y = jnp.arange(8) % 4
    cfgs = [IMACConfig(array_rows=8, array_cols=8, tech=t) for t in ("MRAM", "PCM")]
    res = ev.evaluate_batch(params, x, y, cfgs, n_samples=8, chunk=4)
    assert [(r.error_rate, r.per_layer_power, r.worst_residual) for r in res] == MLP_RECORDED
