#!/usr/bin/env python3
"""Readings that the comparison limits of a cell are set from.

    python bench/control.py --workload <cell> --seeds 1,2,3 --controls 7,8,9

For each seed of `--seeds` it runs the cell's timed path (the program as
the window drives it: the same `run_sweep` calls at the cell's sizes,
as many per seed as a run checks) and prints the numbers the check
compares: the lower readings. For each seed of `--controls` it puts a
lower-precision computation in the program's place and prints the same
numbers: the upper readings.

  parasitic cells  the program's own bfloat16 path (`IMACConfig.dtype`),
                   one precision below the float32 the configuration states;
  ideal cells      the reference, in JAX on the chip, with its two dots at
                   `Precision.HIGH` (three bfloat16 passes), one below the
                   `HIGHEST` the ideal path states; and at `DEFAULT` (one
                   bfloat16 pass) for scale.

Each reading is one line `reading <kind> seed=<s> <number>=<value> ...`;
the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types

import run  # noqa: E402  (bench/run.py: cell lookup and program import)


def ideal_results(cfg, traffic, call, precision):
    """IMACResult-like results of one loop call, computed by a plain JAX
    ideal crossbar in float32 with its two dots at `precision`. (The CPU
    computes every precision in full float32; the chip does not.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchlib import reference

    xs, ys = traffic.inputs(call)
    z_lim = cfg["vdd"] / cfg["neuron"]["z_volt"]
    out = []
    for point in call.points:
        tech = cfg["technologies"][point.tech]
        g_on, g_off = 1.0 / tech["r_low"], 1.0 / tech["r_high"]
        plans = reference.plan_layers(cfg["topology"], point.partitioning)
        a = jnp.asarray(xs, jnp.float32)
        powers = []
        for layer, ((w, b), plan) in enumerate(zip(traffic.params, plans)):
            wb = jnp.concatenate([w, b[None, :]])
            scale = jnp.max(jnp.abs(wb))
            gp = jnp.clip(g_off + jnp.maximum(wb / scale, 0) * (g_on - g_off), g_off, g_on)
            gn = jnp.clip(g_off + jnp.maximum(-wb / scale, 0) * (g_on - g_off), g_off, g_on)
            v = jnp.concatenate([a, jnp.ones((a.shape[0], 1))], 1) * cfg["vdd"]
            i = jnp.matmul(v, gp - gn, precision=precision)
            p = jnp.matmul(v ** 2, gp + gn, precision=precision).sum(1)
            z = jnp.clip(i / ((g_on - g_off) / scale * cfg["vdd"]), -z_lim, z_lim)
            a = z if layer == len(plans) - 1 else jax.nn.sigmoid(z)
            powers.append(float(jnp.mean(p)) + reference.interface_power(plan, cfg["neuron"]))
        errors = int(np.sum(np.asarray(jnp.argmax(a, -1)) != ys))
        out.append(types.SimpleNamespace(per_layer_power=tuple(powers),
                                         error_rate=errors / len(ys)))
    return out


def readings(spec, seed, kind, calls_per_seed, dtype=None, precision=None):
    from benchlib import check, data
    from benchlib.traffic import Traffic

    cfg, mix = spec["config"], spec["mix"]
    params, x_pool, y_pool, _ = data.make_workload(seed, cfg)
    traffic = Traffic(cfg, mix, seed, params, x_pool, y_pool, dtype=dtype)
    calls = [traffic.call(traffic.next_points(), traffic.label)
             for _ in range(calls_per_seed)]
    results_of = None
    if precision is not None:
        results_of = lambda c: ideal_results(cfg, traffic, c, precision)  # noqa: E731
    t0 = time.perf_counter()
    worst = check.compare(traffic, calls, seed, results_of=results_of)
    text = " ".join(f"{k}={v}" for k, v in worst.items() if k != "per_point")
    for name, nums in worst["per_point"]:
        print(f"  point {kind} seed={seed} {name} {nums}", flush=True)
    print(f"reading {kind} seed={seed} {text} reference_s={time.perf_counter() - t0}",
          flush=True)
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--controls", default="")
    ap.add_argument("--calls", type=int, default=0,
                    help="calls per seed (default: the mix's check_calls)")
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)

    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["cell"]["chips"]:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 3
    run.import_program()
    run.use_compile_cache()
    calls = args.calls or int(spec["mix"].get("check_calls", 1))
    parasitic = bool(spec["mix"]["parasitics"])
    for s in filter(None, args.seeds.split(",")):
        readings(spec, int(s), "program", calls)
    for s in filter(None, args.controls.split(",")):
        if parasitic:
            readings(spec, int(s), "control_bf16", calls, dtype=jnp.bfloat16)
        else:
            readings(spec, int(s), "control_high", calls,
                     precision=jax.lax.Precision.HIGH)
            readings(spec, int(s), "control_default", calls,
                     precision=jax.lax.Precision.DEFAULT)
    print(json.dumps({"workload": args.workload, "done": True}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
