"""Multi-device sharded sweep execution.

Covers repro.distributed.sweep (MeshPlan, padding, staging pipeline),
the run_sweep/run_variability ``shard=`` path's bitwise identity with
the single-device engine, the concurrency-safe ResultCache, and the
ledger's mesh tagging.

Single-device hosts run the pure-helper and 1-device-mesh tests; the
genuinely multi-device cases skip unless the process was launched with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
mesh-smoke job does exactly that).
"""
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import IMACConfig
from repro.core.evaluate import IMACResult
from repro.distributed.sweep import (
    MeshPlan,
    as_mesh_plan,
    pad_count,
    pad_stacked,
    shard_put,
    stacked_spec,
    stage_pipeline,
)
from repro.explore import ResultCache, SweepSpec, run_sweep

N_DEVICES = len(jax.devices())
multi_device = pytest.mark.skipif(
    N_DEVICES < 2,
    reason="needs a multi-device mesh "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# ----------------------------------------------------------- pure helpers


def test_pad_count():
    assert pad_count(3, 8) == 8
    assert pad_count(8, 8) == 8
    assert pad_count(9, 8) == 16
    assert pad_count(1, 1) == 1


@pytest.mark.parametrize("c", [1, 3, 5, 7])
def test_pad_stacked_replicates_entry_zero(c):
    x = jnp.arange(c * 6, dtype=jnp.float32).reshape(c, 2, 3)
    padded = pad_stacked(x, 8)
    assert padded.shape == (8, 2, 3)
    np.testing.assert_array_equal(np.asarray(padded[:c]), np.asarray(x))
    for lane in range(c, 8):
        np.testing.assert_array_equal(
            np.asarray(padded[lane]), np.asarray(x[0])
        )


def test_pad_stacked_noop_when_divisible():
    x = jnp.ones((8, 3))
    assert pad_stacked(x, 8) is x
    assert pad_stacked(x, 4) is x


def test_as_mesh_plan_coercions():
    assert as_mesh_plan(None) is None
    assert as_mesh_plan(False) is None
    assert as_mesh_plan(True) == MeshPlan()
    assert as_mesh_plan(4) == MeshPlan(devices=4)
    plan = MeshPlan(devices=2, overlap=False)
    assert as_mesh_plan(plan) is plan
    with pytest.raises(TypeError, match="shard="):
        as_mesh_plan("data")


def test_stage_pipeline_double_buffers():
    """Group i+1 must be staged before group i is yielded."""
    staged = []
    out = []
    for i, item in stage_pipeline(
        ["a", "b", "c"], lambda g: staged.append(g) or g.upper()
    ):
        # By the time we consume item i, item i+1 is already staged.
        assert len(staged) == min(i + 2, 3)
        out.append((i, item))
    assert out == [(0, "A"), (1, "B"), (2, "C")]
    assert staged == ["a", "b", "c"]


def test_stage_pipeline_empty():
    assert list(stage_pipeline([], lambda g: g)) == []


def test_meshplan_shape_str_and_axis_size():
    plan = MeshPlan(devices=1)
    assert plan.axis_size() == 1
    assert plan.shape_str() == "data1"
    if N_DEVICES > 1:
        assert MeshPlan().shape_str() == f"data{N_DEVICES}"


@multi_device
def test_stacked_spec_divisibility_fallback():
    mesh = jax.make_mesh((N_DEVICES,), ("data",))
    # Leading dim divides the mesh axis: sharded on it.
    sharded = stacked_spec(jnp.zeros((N_DEVICES * 2, 4)), mesh)
    assert sharded[0] == "data"
    # Non-divisible leading dim: falls back to replicated, not an error.
    repl = stacked_spec(jnp.zeros((3, 4)), mesh)
    assert all(s is None for s in repl)


@multi_device
def test_shard_put_places_divisible_leaves():
    mesh = jax.make_mesh((N_DEVICES,), ("data",))
    tree = {
        "even": jnp.zeros((N_DEVICES * 2, 3)),
        "odd": jnp.zeros((3, 3)),
        "scalar": 1.5,
    }
    out = shard_put(tree, mesh)
    assert not out["even"].sharding.is_fully_replicated
    assert out["odd"].sharding.is_fully_replicated
    assert out["scalar"] == 1.5


# ------------------------------------------------- bitwise identity (1 dev)


def _assert_results_equal(a, b):
    assert [r.name for r in a] == [r.name for r in b]
    for ra, rb in zip(a, b):
        assert ra.result == rb.result  # NamedTuple: full bitwise equality


def test_sharded_identical_on_one_device(trained_tiny_mlp):
    """shard= on a 1-device mesh still routes through shard_map + the
    solver's pmax cond and must change nothing."""
    params, xte, yte = trained_tiny_mlp
    spec = SweepSpec.grid(IMACConfig(), tech=["MRAM", "PCM"])
    plain = run_sweep(params, xte, yte, spec, n_samples=8, chunk=8)
    sharded = run_sweep(
        params, xte, yte, spec, n_samples=8, chunk=8,
        shard=MeshPlan(devices=1),
    )
    _assert_results_equal(plain, sharded)


def test_small_groups_fall_back(trained_tiny_mlp):
    """Groups below min_group use the plain path (and still match)."""
    params, xte, yte = trained_tiny_mlp
    cfgs = [("solo", IMACConfig(tech="PCM", parasitics=False))]
    plain = run_sweep(params, xte, yte, cfgs, n_samples=8, chunk=8)
    sharded = run_sweep(
        params, xte, yte, cfgs, n_samples=8, chunk=8,
        shard=MeshPlan(devices=1, min_group=2),
    )
    _assert_results_equal(plain, sharded)


# --------------------------------------------- bitwise identity (n devices)


@multi_device
@pytest.mark.parametrize("c", [3, 5, 7])
def test_odd_group_sizes_bitwise_identical(trained_tiny_mlp, c):
    """Non-divisible group sizes: pad lanes must not perturb results."""
    params, xte, yte = trained_tiny_mlp
    cfgs = [
        (f"r{i}", IMACConfig(r_tia=5.0 + 0.5 * i)) for i in range(c)
    ]
    plain = run_sweep(params, xte, yte, cfgs, n_samples=8, chunk=8)
    sharded = run_sweep(params, xte, yte, cfgs, n_samples=8, chunk=8,
                        shard=True)
    _assert_results_equal(plain, sharded)


@multi_device
def test_multi_group_sweep_identical(trained_tiny_mlp):
    """Two structure groups (different array sizes) with the
    largest-first scheduler reordering groups internally: per-point
    results must still land in spec order, bitwise equal."""
    params, xte, yte = trained_tiny_mlp
    spec = SweepSpec.grid(
        IMACConfig(),
        tech=["MRAM", "RRAM", "PCM"],
        array_size=[32, 64],
    )
    plain = run_sweep(params, xte, yte, spec, n_samples=8, chunk=8)
    sharded = run_sweep(params, xte, yte, spec, n_samples=8, chunk=8,
                        shard=True)
    _assert_results_equal(plain, sharded)


@multi_device
def test_sweepspec_carries_shard(trained_tiny_mlp):
    params, xte, yte = trained_tiny_mlp
    spec = SweepSpec.grid(
        IMACConfig(), shard=True, tech=["MRAM", "RRAM", "PCM"],
    )
    plain = run_sweep(
        params, xte, yte,
        SweepSpec.grid(IMACConfig(), tech=["MRAM", "RRAM", "PCM"]),
        n_samples=8, chunk=8,
    )
    sharded = run_sweep(params, xte, yte, spec, n_samples=8, chunk=8)
    _assert_results_equal(plain, sharded)


@multi_device
def test_ideal_path_sharded_predictions_bitwise(trained_tiny_mlp):
    """parasitics=False: predictions stay bitwise; the power einsum's
    reduction order follows the local batch shape, so power agrees only
    to float32 reassociation (the documented ideal-MVM caveat)."""
    params, xte, yte = trained_tiny_mlp
    cfgs = [
        (t, IMACConfig(parasitics=False, tech=t))
        for t in ("MRAM", "RRAM", "CBRAM", "PCM")
    ]
    plain = run_sweep(params, xte, yte, cfgs, n_samples=8, chunk=8)
    sharded = run_sweep(params, xte, yte, cfgs, n_samples=8, chunk=8,
                        shard=True)
    for ra, rb in zip(plain, sharded):
        assert ra.result.accuracy == rb.result.accuracy
        assert ra.result.avg_power == pytest.approx(
            rb.result.avg_power, rel=1e-6
        )
        np.testing.assert_allclose(
            ra.result.per_layer_power, rb.result.per_layer_power,
            rtol=1e-6,
        )


@multi_device
def test_shared_variation_key_sharded(trained_tiny_mlp):
    """A paired variation_key + noise-free sweep shards and matches."""
    params, xte, yte = trained_tiny_mlp
    from repro.core.devices import custom_tech

    noisy = custom_tech(5e3, 1e5, name="VAR", sigma_rel=0.05)
    cfgs = [
        (f"r{i}", IMACConfig(tech=noisy, r_tia=5.0 + i))
        for i in range(3)
    ]
    key = jax.random.PRNGKey(7)
    plain = run_sweep(params, xte, yte, cfgs, n_samples=8, chunk=8,
                      variation_key=key)
    sharded = run_sweep(params, xte, yte, cfgs, n_samples=8, chunk=8,
                        variation_key=key, shard=True)
    _assert_results_equal(plain, sharded)


@multi_device
def test_run_variability_sharded_identical(trained_tiny_mlp):
    from repro.core.devices import custom_tech
    from repro.variability import VariabilitySpec
    from repro.variability.engine import run_variability

    params, xte, yte = trained_tiny_mlp
    cfg = IMACConfig(
        tech=custom_tech(5e3, 1e5, name="VAR", sigma_rel=0.1),
    )
    spec = VariabilitySpec(trials=6, seed=11)
    plain = run_variability(params, xte, yte, cfg, spec, n_samples=8,
                            chunk=8)
    sharded = run_variability(params, xte, yte, cfg, spec, n_samples=8,
                              chunk=8, shard=True)
    assert plain == sharded  # ReliabilityReport NamedTuple equality


@multi_device
def test_per_config_noise_falls_back_sharded_identical(trained_tiny_mlp):
    """Per-trial read-noise draws depend on the full stacked shape, so
    the engine must fall back unsharded — and therefore stay identical."""
    from repro.core.devices import custom_tech
    from repro.variability import VariabilitySpec
    from repro.variability.engine import run_variability

    params, xte, yte = trained_tiny_mlp
    cfg = IMACConfig(
        tech=custom_tech(
            5e3, 1e5, name="VARN", sigma_rel=0.1, read_noise_rel=0.02
        ),
        parasitics=False,
    )
    spec = VariabilitySpec(trials=4, seed=5)
    plain = run_variability(params, xte, yte, cfg, spec, n_samples=8,
                            chunk=8)
    sharded = run_variability(params, xte, yte, cfg, spec, n_samples=8,
                              chunk=8, shard=True)
    assert plain == sharded


# ------------------------------------------------------- cache concurrency


def _fake_result(acc: float) -> IMACResult:
    return IMACResult(
        accuracy=acc, error_rate=1 - acc, avg_power=1e-3, latency=2e-8,
        digital_accuracy=0.97, per_layer_power=(1e-3, 2e-3),
        worst_residual=1e-7, n_samples=16, hp=(13, 4, 3), vp=(4, 3, 1),
    )


def test_cache_concurrent_writers_same_key(tmp_path):
    """Two threads hammering one key: every get sees either a miss or a
    complete entry — never an exception, never a torn read."""
    cache = ResultCache(str(tmp_path / "c"))
    key = "k" * 64
    errors = []

    def hammer(acc):
        try:
            for _ in range(200):
                cache.put(key, _fake_result(acc), name="race")
                got = cache.get(key)
                assert got is not None
                assert got.accuracy in (0.25, 0.75)
        except Exception as e:  # surfaced below; threads swallow asserts
            errors.append(e)

    threads = [
        threading.Thread(target=hammer, args=(a,)) for a in (0.25, 0.75)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    # Last writer won with a complete entry; no temp debris left behind.
    final = cache.get(key)
    assert final is not None and final.n_samples == 16
    assert len(cache) == 1
    assert not [f for f in os.listdir(cache.path) if f.endswith(".tmp")]


def test_cache_tolerates_corrupt_entries(tmp_path):
    cache = ResultCache(str(tmp_path / "c"))
    key = "a" * 64
    # Torn write: invalid JSON.
    with open(cache._file(key), "w") as fh:
        fh.write('{"name": "torn", "resu')
    assert cache.get(key) is None
    # Valid JSON, wrong shape (missing result fields).
    with open(cache._file(key), "w") as fh:
        json.dump({"kind": "imac", "result": {"accuracy": 0.5}}, fh)
    assert cache.get(key) is None
    assert cache.misses == 2 and cache.hits == 0
    # put heals the slot.
    cache.put(key, _fake_result(0.5))
    assert cache.get(key).accuracy == 0.5


def test_cache_corrupt_entry_counts_event(tmp_path):
    obs.enable()
    cache = ResultCache(str(tmp_path / "c"))
    key = "b" * 64
    with open(cache._file(key), "w") as fh:
        fh.write("not json at all")
    assert cache.get(key) is None
    assert len(obs.events("cache_corrupt_entry")) == 1
    assert "cache_corrupt_entry_total" in obs.snapshot()


# ---------------------------------------------------- ledger mesh tagging


def test_mesh_context_scopes_and_restores():
    from repro.obs import ledger

    assert ledger.current_mesh_context() is None
    with ledger.mesh_context("data8"):
        assert ledger.current_mesh_context() == "data8"
        with ledger.mesh_context(None):  # None leaves the tag as-is
            assert ledger.current_mesh_context() == "data8"
    assert ledger.current_mesh_context() is None


def test_ledger_entries_carry_mesh_shape(tmp_path):
    from repro.obs import ledger

    with ledger.mesh_context("data8"):
        entry = ledger.make_entry("sweep", [("row", 1.0, "")])
    assert entry["mesh_shape"] == "data8"
    plain = ledger.make_entry("sweep", [("row", 1.0, "")])
    assert plain["mesh_shape"] is None
    # Env matching: sharded history never gates single-device runs.
    assert ledger.matching([entry], env_of=plain) == []
    assert ledger.matching([entry], env_of=entry) == [entry]
    # Entries predating the key read back as None and keep matching
    # unsharded runs.
    legacy = {k: v for k, v in plain.items() if k != "mesh_shape"}
    assert ledger.matching([legacy], env_of=plain) == [legacy]


def test_sharded_run_sweep_tags_ledger(
    trained_tiny_mlp, tmp_path, monkeypatch
):
    from repro.obs import ledger

    params, xte, yte = trained_tiny_mlp
    path = str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv("REPRO_OBS_LEDGER", path)
    obs.enable()
    run_sweep(
        params, xte, yte,
        [("a", IMACConfig(parasitics=False)),
         ("b", IMACConfig(parasitics=False, r_tia=7.0))],
        n_samples=8, chunk=8, shard=MeshPlan(devices=1),
    )
    run_sweep(
        params, xte, yte, [("a", IMACConfig(parasitics=False))],
        n_samples=8, chunk=8,
    )
    entries = ledger.load(path)
    assert len(entries) == 2
    assert entries[0]["mesh_shape"] == "data1"
    assert "mesh=data1" in entries[0]["rows"][0]["derived"]
    assert entries[1]["mesh_shape"] is None
    # The metrics snapshot rode along: the group program's build counters
    # and the solve telemetry.
    metrics = entries[0]["metrics"]
    assert metrics["jit_traces_total"]["series"][0]["value"] > 0
    assert metrics["solver_chunks_total"]["series"][0]["value"] > 0
    assert "sweep_points_per_s" not in metrics
