"""Kind `variability`: Monte-Carlo yield studies of one design point.

Each call (a study) is one `repro.variability.run_variability` call on the
configuration's one partitioning in the mix's `technology`: `trials`
freshly sampled chips (lognormal programming variation `sigma_rel`,
stuck-at-G_off faults `p_stuck_off`, per-trial read noise
`read_noise_rel`), each over the next `n_samples` inputs of the seeded
digit pool, `chunk` inputs per solve. Its `VariabilitySpec.seed` is drawn
from the run's seed and the call's index, so every study samples new
chips. Studies run back to back (a closed loop). A call's points are its
trials, all of one structure group; its results are the report's
per-trial accuracy and power. Inputs and weights are the `sweep` kind's
(`benchlib.data`).

The check reruns `check_trials` trials, drawn from the run's seed, of
`check_calls` seeded calls through the float64 reference, each with its
conductances, stuck faults and read noise rebuilt from the study's seed
(`benchlib.trials`) and every tile solved for that trial:

  dev_power_rel_err  the largest gap, over the checked trials, between the
                     program's trial power and the reference's, over the
                     reference's device power;
  error_count_diff   the largest gap in misclassified inputs.

A study whose report holds another number of trials than it drew reads
infinity in both, as does a trial whose numbers are not finite.

Controls: `control_bf16`, the program's bfloat16 path (`IMACConfig.dtype`),
one precision below the float32 the configuration states; and
`control_wrong_key`, the program with the first checked trial of every
study drawn with another key.
"""
from __future__ import annotations

import dataclasses
import math
import time
import types

import jax
import numpy as np

from benchlib import data, reference, trials
from benchlib.points import Point, design_points, program_config
from benchlib.traffic import Call, Traffic, failed, on_digits  # noqa: F401  (failed: the kind's)

NUMBERS = ("dev_power_rel_err", "error_count_diff")
CONTROLS = ("control_bf16", "control_wrong_key")


@dataclasses.dataclass
class StudyCall(Call):
    """One `run_variability` call; `points` are its trials."""

    spec: object = None    # the call's VariabilitySpec


class Study:
    """Drives Monte-Carlo studies; warm-up, window and inputs as `Traffic`'s."""

    label = "study"
    warm_up = Traffic.warm_up
    window = Traffic.window
    inputs = Traffic.inputs

    def __init__(self, cfg: dict, mix: dict, seed: int, params, x_pool, y_pool,
                 dtype=None, rekey=None):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.params, self.x_pool, self.y_pool = params, x_pool, y_pool
        self.n_samples = int(mix["n_samples"])
        self.chunk = int(mix["chunk"])
        self.parasitics = bool(mix["parasitics"])
        self.trials = int(mix["trials"])
        (point,) = [p for p in design_points(cfg) if p.tech == mix["technology"]]
        self.point = point
        self.program = program_config(cfg, point, parasitics=self.parasitics,
                                      dtype=dtype)
        self.points = [Point(f"{point.name}/t{t}", point.tech, point.partitioning,
                             point.group) for t in range(self.trials)]
        self.slices = x_pool.shape[0] // self.n_samples
        self.rekey = rekey     # a trial drawn with another key (control)
        self._next_slice = 0
        self._next_study = 0

    def spec(self, index: int):
        from repro.variability import VariabilitySpec

        return VariabilitySpec(
            trials=self.trials, seed=data.derive(self.seed, 5, index),
            sigma_rel=float(self.mix["sigma_rel"]),
            read_noise_rel=float(self.mix["read_noise_rel"]),
            p_stuck_off=float(self.mix["p_stuck_off"]))

    def next_points(self) -> "list[Point]":
        return self.points

    def warmup_points(self) -> "list[list[Point]]":
        return [self.points]

    def call(self, points: "list[Point]", label: str) -> StudyCall:
        from repro.variability import run_variability

        spec = self.spec(self._next_study)
        self._next_study += 1
        offset = (self._next_slice % self.slices) * self.n_samples
        self._next_slice += 1
        xs = self.x_pool[offset:offset + self.n_samples]
        ys = self.y_pool[offset:offset + self.n_samples]
        keys = None
        if self.rekey is not None:
            keys = trials.trial_keys(spec.seed, spec.trials)
            keys = keys.at[self.rekey].set(jax.random.fold_in(keys[self.rekey], 1))
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(label):
            report = run_variability(self.params, xs, ys, self.program, spec,
                                     keys=keys, n_samples=self.n_samples,
                                     chunk=self.chunk)
        t1 = time.perf_counter()
        results = [types.SimpleNamespace(accuracy=a, avg_power=p) for a, p in
                   zip(report.per_trial_accuracy, report.per_trial_power)]
        return StudyCall(points, offset, t0, t1, results, spec)


def checked_trials(seed: int, n_trials: int, k: int) -> "list[int]":
    """The trial indices the check takes from each checked call."""
    rng = np.random.default_rng(data.derive(seed, 6))
    return sorted(int(t) for t in rng.choice(n_trials, size=min(k, n_trials),
                                             replace=False))


def build(cfg: dict, mix: dict, seed: int, control=None):
    """(studies, notes): the digit pool and trained weights of the seed."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    kw = {}
    if control == "control_bf16":
        import jax.numpy as jnp

        kw["dtype"] = jnp.bfloat16
    elif control == "control_wrong_key":
        kw["rekey"] = checked_trials(seed, int(mix["trials"]),
                                     int(mix["check_trials"]))[0]
    return on_digits(cfg, mix, seed, Study, **kw)


def numbers_for(result, ref, n_samples: int) -> dict:
    power, acc = result.avg_power, result.accuracy
    if not (math.isfinite(power) and math.isfinite(acc)):
        return {k: math.inf for k in NUMBERS}
    return {
        "dev_power_rel_err": abs(power - float(np.sum(ref.layer_power)))
        / float(np.sum(ref.layer_device_power)),
        "error_count_diff": float(abs(round((1.0 - acc) * n_samples) - ref.errors)),
    }


def compare(traffic: Study, calls, seed: int) -> dict:
    """Worst value of each number over the checked trials of the sampled calls."""
    rng = np.random.default_rng(data.derive(seed, 4))
    n = min(int(traffic.mix.get("check_calls", 1)), len(calls))
    chosen = sorted(rng.choice(len(calls), size=n, replace=False))
    picked = checked_trials(seed, traffic.trials, int(traffic.mix["check_trials"]))
    cfg = traffic.cfg
    tech = cfg["technologies"][traffic.point.tech]
    g_on, g_off = 1.0 / tech["r_low"], 1.0 / tech["r_high"]
    params = [(np.asarray(w, np.float64), np.asarray(b, np.float64))
              for w, b in traffic.params]
    base = [reference.map_layer(w, b, tech) for w, b in params]
    fan_outs = [w.shape[1] for w, _ in params]
    worst = {k: 0.0 for k in NUMBERS}
    per_point = []
    for ci in chosen:
        call = calls[int(ci)]
        spec = call.spec
        xs, ys = traffic.inputs(call)
        keys = trials.trial_keys(spec.seed, spec.trials)
        noise = trials.read_noise(spec.seed, spec.trials, traffic.n_samples,
                                  traffic.chunk, fan_outs)
        complete = len(call.results) == len(call.points) == spec.trials
        for t in picked:
            if not complete:
                nums = {k: math.inf for k in NUMBERS}
            else:
                layers = trials.conductances(base, keys[t], g_on, g_off,
                                             spec.sigma_rel, spec.p_stuck_on,
                                             spec.p_stuck_off)
                ref = reference.forward(
                    cfg, traffic.point.partitioning, layers, xs, ys,
                    parasitics=traffic.parasitics,
                    read_noise=[(spec.read_noise_rel, d[t]) for d in noise])
                nums = numbers_for(call.results[t], ref, traffic.n_samples)
            for k, v in nums.items():
                worst[k] = max(worst[k], v)
            per_point.append((call.points[t].name, nums))
    worst["checked_points"] = len(per_point)
    worst["per_point"] = per_point
    return worst

