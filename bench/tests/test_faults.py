"""A run whose timed path is broken underneath comes out not correct.

Each test skips the look for a chip, drives the rest of a run at a small
size on the CPU through `run.execute`, with one fault planted in the
program below the harness, and reads `correct` from the result line:

  half_batch  every evaluation sees only the first half of its inputs;
              accuracy and power are means over that half;
  altered     the answers are changed where they are produced: each
              design point's output-layer power, by 1%;
  miscounted  each design point misclassifies three more inputs, its
              power untouched;
  last_slot   only the last design point of each call (one slot of the
              stacked group program) has its output-layer power changed;
  no_exchange (four devices) the sharded sweep reads every chip's results
              from chip 0: the gather of results between chips is left out.

The Monte-Carlo cell (`tableIV-mram-mc64`) takes the first three in its
solve, and its own:

  wrong_key   the first checked trial of every study is drawn with another
              key;
  no_stuck    the stuck-at faults are left out;
  half_trials the report summarises only the first half of the trials;
  no_read_noise  the read noise is left out, at the cell's own 1%
              (`test_study_read_noise_follows_the_program`): it moves a
              trial's power through the activations it changes.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

import run
from conftest import run_small, small_study

BENCH = Path(__file__).resolve().parents[1]


def half_batch(real):
    def evaluate_batch(params, x, y, cfgs, *, n_samples=None, **kw):
        n = (n_samples or x.shape[0]) // 2
        return real(params, x[:n], y[:n], cfgs, n_samples=n, **kw)
    return evaluate_batch


def altered(real):
    def evaluate_batch(*args, **kw):
        out = []
        for r in real(*args, **kw):
            power = list(r.per_layer_power)
            power[-1] *= 1.01
            out.append(r._replace(per_layer_power=tuple(power),
                                  avg_power=sum(power)))
        return out
    return evaluate_batch


def miscounted(real):
    def evaluate_batch(*args, **kw):
        out = []
        for r in real(*args, **kw):
            error_rate = r.error_rate + 3 / r.n_samples
            out.append(r._replace(error_rate=error_rate, accuracy=1 - error_rate))
        return out
    return evaluate_batch


def last_slot(real):
    def evaluate_batch(*args, **kw):
        out = list(real(*args, **kw))
        power = list(out[-1].per_layer_power)
        power[-1] *= 1.01
        out[-1] = out[-1]._replace(per_layer_power=tuple(power),
                                   avg_power=sum(power))
        return out
    return evaluate_batch


CELLS = [("tableIV-sweep", None, 4), ("grid-sweep", ["32x32-hp16"], 4),
         ("grid-screen-loop", None, 16)]


@pytest.mark.parametrize("workload,groups,n", CELLS)
def test_sound_run_is_correct(small, capsys, workload, groups, n):
    result = run_small(small(workload, n, groups), capsys)
    assert result["correct"] is True
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", [half_batch, altered, miscounted, last_slot])
@pytest.mark.parametrize("workload,groups,n", CELLS)
def test_fault_is_caught(small, capsys, monkeypatch, workload, groups, n, fault):
    import repro.explore.engine as engine

    monkeypatch.setattr(engine, "evaluate_batch", fault(engine.evaluate_batch))
    result = run_small(small(workload, n, groups), capsys)
    assert result["correct"] is False


NO_EXCHANGE = textwrap.dedent("""
    import argparse, json, sys
    sys.path.insert(0, {tests!r}); sys.path.insert(0, {bench!r})
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import run
    run.import_program()
    from conftest import small_spec
    fault = {fault!r}
    if fault:
        real = jax.shard_map

        def shard_map(f, *, mesh, in_specs, out_specs, **kw):
            n = mesh.devices.size
            local = real(f, mesh=mesh, in_specs=in_specs,
                         out_specs=tuple(P() for _ in out_specs), **kw)

            def call(*a):
                outs = local(*a)
                return tuple(o if s == P() else jnp.concatenate([o] * n)
                             for o, s in zip(outs, out_specs))
            return call

        jax.shard_map = shard_map
    spec = small_spec("grid-sweep-4chip", 4, ["32x32-hp16"])
    args = argparse.Namespace(workload="grid-sweep-4chip", seed=2**31 + 9,
                              seconds=0.01, trace=0)
    sys.exit(run.execute(args, spec, jax.devices()))
""")


@pytest.mark.parametrize("fault", [False, True])
def test_no_exchange_between_chips_is_caught(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = NO_EXCHANGE.format(tests=str(BENCH / "tests"), bench=str(BENCH),
                              fault=fault)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is (not fault)


def wrong_key(monkeypatch, spec, seed):
    import repro.variability.engine as mc

    mix = spec["mix"]
    t = run.load_kind("variability").checked_trials(
        seed, mix["trials"], mix["check_trials"])[0]
    real = mc.trial_keys

    def trial_keys(vspec):
        keys = real(vspec)
        return keys.at[t].set(jax.random.fold_in(keys[t], 1))
    monkeypatch.setattr(mc, "trial_keys", trial_keys)


def no_stuck(monkeypatch, spec, seed):
    import repro.variability.engine as mc

    monkeypatch.setattr(mc, "apply_stuck_faults", lambda g, *_: g)


def half_trials(monkeypatch, spec, seed):
    import repro.variability.engine as mc

    real = mc.summarize
    monkeypatch.setattr(mc, "summarize",
                        lambda results, **kw: real(results[: len(results) // 2], **kw))


def no_read_noise(real):
    def evaluate_batch(*args, noise_key=None, **kw):
        return real(*args, noise_key=None, **kw)
    return evaluate_batch


SEED = 2**31 + 5


def test_study_sound_run_is_correct(capsys):
    result = run_small(small_study(), capsys, seed=SEED)
    assert result["correct"] is True
    assert result["attempted"] == 4 and result["failed"] == 0


@pytest.mark.parametrize("fault", [half_batch, altered, miscounted])
def test_study_fault_in_the_solve_is_caught(capsys, monkeypatch, fault):
    import repro.variability.engine as mc

    monkeypatch.setattr(mc, "evaluate_batch", fault(mc.evaluate_batch))
    assert run_small(small_study(), capsys, seed=SEED)["correct"] is False


@pytest.mark.parametrize("fault", [wrong_key, no_stuck, half_trials])
def test_study_fault_in_the_trials_is_caught(capsys, monkeypatch, fault):
    spec = small_study()
    fault(monkeypatch, spec, SEED)
    assert run_small(spec, capsys, seed=SEED)["correct"] is False


@pytest.mark.parametrize("noise_left_out", [False, True])
def test_study_read_noise_follows_the_program(capsys, monkeypatch, noise_left_out):
    import repro.variability.engine as mc

    if noise_left_out:
        monkeypatch.setattr(mc, "evaluate_batch", no_read_noise(mc.evaluate_batch))
    result = run_small(small_study(), capsys, seed=SEED)
    assert result["correct"] is (not noise_left_out)
