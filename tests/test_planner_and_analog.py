"""Deployment planner + analog-serving-mode tests."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.core.devices import PCM
from repro.core.neurons import get_neuron
from repro.core.planner import plan_arch
from repro.models import build_model


def test_planner_all_archs():
    for name, cfg in ARCHS.items():
        rep = plan_arch(cfg, tech="PCM", array_rows=512, array_cols=512)
        assert rep.total_tiles > 0
        assert rep.total_devices > 2 * cfg.n_params() * 0.5  # diff pairs
        assert rep.est_power_w > 0 and rep.area_mm2 > 0


def test_planner_device_count_tracks_params():
    yi = plan_arch(ARCHS["yi-9b"], "PCM")
    mini = plan_arch(ARCHS["minicpm-2b"], "PCM")
    assert yi.total_devices > mini.total_devices


def test_planner_tech_power_ordering():
    """Paper Table IV at LLM scale: PCM cheapest, RRAM most expensive."""
    powers = {
        t: plan_arch(ARCHS["yi-9b"], t).est_power_w
        for t in ("MRAM", "RRAM", "CBRAM", "PCM")
    }
    assert powers["PCM"] == min(powers.values())
    assert powers["RRAM"] == max(powers.values())


def test_planner_partition_arithmetic():
    rep = plan_arch(ARCHS["granite-3-8b"], "PCM", 512, 512)
    mat = {p.name: p for p in rep.matrices}
    # 4096 -> 4096 projection on 512x512: ceil(4097/512)=9, ceil(4096/512)=8
    assert (mat["wq"].hp, mat["wq"].vp) == (9, 8)


def test_analog_mvm_mode_close_to_digital():
    cfg = dataclasses.replace(
        get_config("granite-3-8b").reduced(), n_layers=2,
        param_dtype="float32", compute_dtype="float32",
    )
    digital = build_model(cfg, remat=False)
    analog = build_model(
        dataclasses.replace(cfg, analog_mvm=True, analog_tech="PCM"),
        remat=False,
    )
    params = digital.init(jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab),
    }
    ld, _ = digital.forward(params, batch)
    la, _ = analog.forward(params, batch)
    # Analog quantisation perturbs but must stay correlated.
    d = np.asarray(ld).reshape(-1)
    a = np.asarray(la).reshape(-1)
    corr = np.corrcoef(d, a)[0, 1]
    assert corr > 0.95, corr
    assert not np.allclose(d, a)  # the non-idealities are actually applied


def test_planner_counts_routed_experts_at_their_activity():
    """DeepSeek-V3: 58 MoE layers of 256 routed experts, 8 a token, and one
    shared expert. Every routed tile exists, but draws power for 8/256 of the
    tokens; the shared expert's for all of them."""
    cfg = ARCHS["deepseek-v3-671b"]
    rep = plan_arch(cfg, "PCM")
    mat = {p.name: p for p in rep.matrices}
    assert (mat["moe_gate"].count, mat["moe_gate"].activity) == (58 * 256, 8 / 256)
    assert (mat["shared_gate"].count, mat["shared_gate"].activity) == (58, 1.0)
    all_on = plan_arch(dataclasses.replace(cfg, experts_per_token=256), "PCM")
    assert rep.total_tiles == all_on.total_tiles
    assert rep.total_devices == all_on.total_devices
    routed = [p for p in all_on.matrices if p.name.startswith("moe_")]
    assert all(p.activity == 1.0 for p in routed)
    p_device = 0.5 * (PCM.g_on + PCM.g_off) * (0.5 * 0.8) ** 2
    neuron = get_neuron("sigmoid")
    routed_w = sum(p.devices * p_device + p.fan_out * p.count * p.hp * 2 * neuron.p_amp
                   + p.fan_out * p.count * neuron.p_neuron for p in routed)
    assert rep.est_power_w == pytest.approx(
        all_on.est_power_w - (1 - 8 / 256) * routed_w, rel=1e-12)
