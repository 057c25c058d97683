"""The comparison that decides `correct`.

Once the window has closed, a sample of its calls drawn from the seed is
run again through the float64 reference (`benchlib.reference`) on the
same inputs. Both numbers have a limit in `bench/limits/<workload>.json`
and decide `correct`:

  dev_power_rel_err  the largest gap, over the sampled points and their
                     layers, between the program's layer power and the
                     reference's, over the reference's device power (the
                     amplifier and neuron share is a constant that both
                     add, and would only dilute the gap). Layer power
                     depends on the mapping, on every tile's solve, and on
                     the activations the earlier layers passed on;
  error_count_diff   the largest gap, over the sampled points, between the
                     program's and the reference's count of misclassified
                     inputs: what the output layer's recombination,
                     readout and argmax decide. Not 0 on every sound run:
                     an input whose two top outputs all but tie in the
                     reference can fall either way in float32.

A point whose results are missing, or not finite, reads infinity.
"""
from __future__ import annotations

import math

import numpy as np

from benchlib import data, reference

NUMBERS = ("dev_power_rel_err", "error_count_diff")


def sample(traffic, calls, seed: int) -> "list[tuple[object, int]]":
    """(call, point index) pairs to check, drawn from the seed.

    `check_calls` calls of the window, every design point of each.
    """
    rng = np.random.default_rng(data.derive(seed, 4))
    n = min(int(traffic.mix.get("check_calls", 1)), len(calls))
    chosen = sorted(rng.choice(len(calls), size=n, replace=False))
    out = []
    for ci in chosen:
        call = calls[int(ci)]
        out.extend((call, i) for i in range(len(call.points)))
    return out


def numbers_for(result, ref, n_samples: int) -> dict:
    power = np.asarray(getattr(result, "per_layer_power", ()), np.float64)
    if power.shape != ref.layer_power.shape or not np.all(np.isfinite(power)):
        return {k: math.inf for k in NUMBERS}
    errors = result.error_rate * n_samples
    if not math.isfinite(errors):
        return {k: math.inf for k in NUMBERS}
    return {
        "dev_power_rel_err": float(np.max(
            np.abs(power - ref.layer_power) / ref.layer_device_power)),
        "error_count_diff": float(abs(round(errors) - ref.errors)),
    }


def compare(traffic, calls, seed: int) -> dict:
    """Worst value of each number over the sampled calls and points."""
    params = [(np.asarray(w, np.float64), np.asarray(b, np.float64))
              for w, b in traffic.params]
    worst = {k: 0.0 for k in NUMBERS}
    checked, per_point = 0, []
    for call, i in sample(traffic, calls, seed):
        xs, ys = traffic.inputs(call)
        point = call.points[i]
        ref = reference.evaluate_point(
            traffic.cfg, {"tech": point.tech, "partitioning": point.partitioning},
            params, xs, ys, parasitics=traffic.parasitics)
        got = call.results[i] if i < len(call.results) else None
        nums = numbers_for(got, ref, traffic.n_samples)
        for k, v in nums.items():
            worst[k] = max(worst[k], v)
        checked += 1
        per_point.append((point.name, nums))
    worst["checked_points"] = checked
    worst["per_point"] = per_point
    return worst
