"""The `moe` kind at a CPU size: a sound run is correct, each planted fault
and each control is not, and the work count is the problem's.

The cell is cut to hidden 256, expert width 64, 16 routed experts in 4
groups (2 kept), 4 a token, 4 held, on 8x8 tiles; its check keeps its
blocks (2 of gate, 2 of up, 4 of down). Faults, each planted in the
program below the harness:

  dropped        a held expert's tokens are not computed;
  no_group_limit the router keeps every group (a plain top-k);
  scaled_out     each token's partial output is 1% larger;
  block_power    one checked block's tile powers are 1% higher;
  scale_kept     the per-token input scale is not undone after sensing;
and the kind's two controls (`control_bf16`, `control_no_group_limit`).
"""
import argparse
import json

import jax
import numpy as np
import pytest

import run
from benchlib import moe_work

SEED = 2**31 + 11
CELL = "dsv3-moe-ep32-b8"


def small_moe() -> dict:
    spec = run.load_cell(CELL)
    spec["config"].update(hidden_size=256, moe_intermediate_size=64, n_routed_experts=16,
                          n_group=4, topk_group=2, num_experts_per_tok=4, experts_held=4)
    spec["config"]["imac"].update(array_rows=8, array_cols=8)
    return spec


@pytest.fixture(autouse=True)
def fresh_programs():
    """Faults are planted in traced code: no program may outlive a test."""
    from repro.core import evaluate

    evaluate.clear_program_cache()
    yield
    evaluate.clear_program_cache()


def run_moe(capsys, spec=None, seed=SEED, trace=0) -> dict:
    spec = spec or small_moe()
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.01, trace=trace)
    assert run.execute(args, spec, jax.devices()) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    result = run_moe(capsys)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 8
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert checks["route_mismatch"] == 0
    assert 0 < checks["z_rel_err"] < 1e-4


def test_traced_run_reports_every_moe_metric(capsys):
    result = run_moe(capsys, trace=1)
    for name in ("moe_route_ms_per_token", "moe_pad_pct", "gs_sweeps_per_solve",
                 "jit_traces_per_point.batch", "program_cache_hit_pct.batch",
                 "host_ms_per_point.batch", "device_wait_ms_per_point.batch"):
        assert name in result["metrics"], name
    assert result["metrics"]["jit_traces_per_point.batch"]["value"] == 0.0
    assert result["metrics"]["program_cache_hit_pct.batch"]["value"] == 100.0


def dropped(monkeypatch, kind):
    import repro.core.evaluate as ev

    real = ev.dispatch

    def dispatch(idx, weight, held):
        return real(idx, weight, held)[1:]
    monkeypatch.setattr(ev, "dispatch", dispatch)


def no_group_limit(monkeypatch, kind):
    import dataclasses

    import repro.core.evaluate as ev

    real = ev.route
    monkeypatch.setattr(ev, "route", lambda x, w, b, spec: real(
        x, w, b, dataclasses.replace(spec, topk_group=spec.n_group)))


def scaled_out(monkeypatch, kind):
    import repro.core.evaluate as ev

    real = ev.evaluate_moe

    def evaluate_moe(*args, **kw):
        res = real(*args, **kw)
        return res._replace(out=res.out * 1.01)
    monkeypatch.setattr(ev, "evaluate_moe", evaluate_moe)


def block_power(monkeypatch, kind):
    """The first checked down block of the sound run draws 1% more, in every
    pair (the same seed checks the same blocks)."""
    import re

    import repro.core.evaluate as ev

    traffic, _ = kind.build(small_moe()["config"], small_moe()["mix"], SEED)
    traffic.warm_up()
    calls, _ = traffic.window(0.01, traffic.label)
    name = kind.compare(traffic, calls, SEED)["per_point"][0][0]
    j = int(re.search(r"'down': \[(\d+)", name).group(1))
    cfg = small_moe()["config"]
    vp = cfg["hidden_size"] // cfg["imac"]["array_cols"]
    hp = cfg["moe_intermediate_size"] // cfg["imac"]["array_rows"]
    tiles = [h * vp + j for h in range(hp)]
    tiles += [hp * vp + t for t in tiles]
    real = ev.evaluate_moe

    def evaluate_moe(*args, **kw):
        res = real(*args, **kw)
        pairs = []
        for p in res.pairs:
            power = p.detail.tile_power_down.copy()
            power[tiles] *= 1.01
            pairs.append(p._replace(detail=p.detail._replace(tile_power_down=power)))
        return res._replace(pairs=tuple(pairs))
    monkeypatch.setattr(ev, "evaluate_moe", evaluate_moe)


def scale_kept(monkeypatch, kind):
    import jax.numpy as jnp

    import repro.core.imac as imac

    real = imac.matrix_forward

    def matrix_forward(g, k, plan, cp, x, **kw):
        peak = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        z, power, sweeps = real(g, k, plan, cp, x, **kw)
        return z / peak, power, sweeps
    import repro.core.evaluate as ev
    monkeypatch.setattr(ev, "matrix_forward", matrix_forward)


@pytest.mark.parametrize("fault", [dropped, no_group_limit, scaled_out, block_power,
                                   scale_kept])
def test_fault_is_caught(capsys, monkeypatch, fault):
    fault(monkeypatch, run.load_kind("moe"))
    result = run_moe(capsys)
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("control", ["control_bf16", "control_no_group_limit"])
def test_control_is_not_correct(capsys, monkeypatch, control):
    kind = run.load_kind("moe")
    real = kind.build
    monkeypatch.setattr(kind, "build", lambda cfg, mix, seed, control=None, c=control:
                        real(cfg, mix, seed, control=c))
    monkeypatch.setattr(run, "load_kind", lambda name: kind)
    result = run_moe(capsys)
    assert result["correct"] is False, result["checks"]


def test_work_counts_21504_systems_a_pair():
    cfg = run.load_cell(CELL)["config"]
    assert moe_work.pair_systems(cfg) == 21504
    w = moe_work.call_work(cfg, pairs=10, experts=3)
    assert w["ops"] == 10 * 21504 * 64 * 64 * 48 * 26
    assert w["bytes"] == 4 * (3 * 21504 * 64 * 64 + 10 * 21504 * (64 + 64))


def test_reference_block_solve_is_the_sparse_lu():
    """The MoE reference's exact block elimination against the sparse LU of
    `benchlib.reference.TileSolver`, on random tiles with absent devices."""
    from benchlib import moe_reference, reference

    rng = np.random.default_rng(5)
    for shape in [(64, 64), (8, 8), (5, 3), (1, 4), (4, 1)]:
        g = rng.uniform(1e-6, 2e-5, (3,) + shape)
        g[rng.random(g.shape) < 0.05] = 0.0
        v = 0.3 * rng.standard_normal((3, shape[0]))
        i_out, power = moe_reference.solve_tiles(g, v, 13.8, 100.0, 10.0)
        for t in range(3):
            i_lu, p_lu = reference.TileSolver(g[t], 13.8, 100.0, 10.0).solve(v[t][None])
            np.testing.assert_allclose(i_out[t], i_lu[0], rtol=1e-9, atol=1e-18)
            assert power[t] == pytest.approx(p_lu[0], rel=1e-9)
