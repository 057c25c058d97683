#!/usr/bin/env python3
"""Run the IMAC simulator's main path once on a TPU and check the results.

    python chip_smoke.py                # one chip: solver, pipeline, engines
    python chip_smoke.py --four-chips   # four chips: sharded sweep only

Phases (one process; nothing here starts a child that touches JAX):

  solver    the paper's batched-tiles shape (104 tiles x 64 samples of
            32x32 MRAM conductances) through the "scan", "pallas" and
            "fused" backends, compiled (never interpret mode); the
            backends agree to 1e-3 relative and a few tiles match an
            independent float64 NumPy dense-MNA solve. One 512x512 tile
            (Table III's top row) goes through "fused", which falls back
            to "pallas" past the VMEM budget, and is compared with "scan".
  pipeline  train the 400-120-84-10 MLP from a seed, deploy it on MRAM
            32x32 tiles with parasitics and evaluate it through
            `evaluate_batch` under "scan" and "fused".
  engines   `run_sweep` over the Table III x Table IV grid,
            `run_variability` and `run_transient`, once each.
  shard     (--four-chips) a sweep sharded over four devices against the
            same sweep unsharded, with one structure group whose size is
            not a multiple of four.

Every phase prints its lines; the last line of standard output is one
JSON object naming the device. Any failure exits non-zero. Without a TPU
the script refuses to run: it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0
RTOL_BACKENDS = 1e-3     # README: iterative backends' i_out tolerance
RTOL_SHARD_POWER = 1e-7  # README: sharded vs unsharded power


def say(phase: str, **fields) -> None:
    text = " ".join(
        f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in fields.items()
    )
    print(f"[{phase}] {text}", flush=True)


def timed(fn, *args):
    """(output, first-call seconds, warm-call seconds), device-synced."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, first, time.perf_counter() - t0


def require(ok, message) -> None:
    """Fail the run (a check that `python -O` does not strip)."""
    if not ok:
        raise RuntimeError(f"check failed: {message}")


def check_compiled() -> None:
    """The Pallas backends must run compiled, not in interpret mode."""
    from repro import obs
    from repro.core.backends import resolve_interpret

    require(resolve_interpret(None) is False, "Pallas would run interpreted")
    interp = [
        e for e in obs.events("backend_fallback")
        if e["fields"].get("cause") == "interpret_mode"
    ]
    require(not interp, f"interpret-mode fallback recorded: {interp}")


def dense_mna_i_out(g, v, cp) -> np.ndarray:
    """Column currents of one tile from a float64 dense MNA solve.

    Written against the circuit, not the library: row nodes r(i, j),
    column nodes c(i, j); wire segments between neighbours, a device
    between r(i, j) and c(i, j), drivers into r(i, 0) through r_source,
    TIAs from c(M-1, j) to ground through r_tia.
    """
    g = np.asarray(g, np.float64)
    v = np.asarray(v, np.float64)
    m, n = g.shape
    r = np.arange(m * n).reshape(m, n)
    c = r + m * n
    a = np.zeros((2 * m * n, 2 * m * n))
    rhs = np.zeros(2 * m * n)

    def stamp(p, q, cond):
        np.add.at(a, (p, p), cond)
        np.add.at(a, (q, q), cond)
        np.add.at(a, (p, q), -cond)
        np.add.at(a, (q, p), -cond)

    stamp(r[:, :-1].ravel(), r[:, 1:].ravel(), 1.0 / cp.r_row)
    stamp(c[:-1, :].ravel(), c[1:, :].ravel(), 1.0 / cp.r_col)
    stamp(r.ravel(), c.ravel(), g.ravel())
    a[r[:, 0], r[:, 0]] += 1.0 / cp.r_source
    rhs[r[:, 0]] += v / cp.r_source
    a[c[-1], c[-1]] += 1.0 / cp.r_tia
    x = np.linalg.solve(a, rhs)
    return x[c[-1]] / cp.r_tia


def solver_phase(tiles=104, size=32, batch=64, big=512) -> None:
    import jax

    from repro import obs
    from repro.core.devices import MRAM
    from repro.core.solver import (
        CircuitParams,
        SolveOptions,
        solve_crossbar,
        solve_ideal,
        suggest_iters,
    )
    from repro.kernels.gs_fused.ops import fused_lane_block

    t_phase = time.perf_counter()
    key_g, key_v, key_big = jax.random.split(jax.random.PRNGKey(SEED), 3)
    g = jax.random.uniform(
        key_g, (tiles, size, size), minval=MRAM.g_off, maxval=MRAM.g_on
    )
    v = jax.random.uniform(key_v, (batch, tiles, size), maxval=0.8)
    cp = CircuitParams(gs_iters=suggest_iters(size, size))
    say("solver", shape=f"{tiles}x{batch}x{size}x{size}", sweeps=cp.gs_iters,
        lane_block=fused_lane_block(size, size))

    outs = {}
    for backend in ("scan", "pallas", "fused"):
        options = SolveOptions(backend=backend)
        fn = jax.jit(
            lambda g, v, o=options: solve_crossbar(g[None], v, cp, options=o)
        )
        sol, first, warm = timed(fn, g, v)
        outs[backend] = np.asarray(sol.i_out)
        require(np.all(np.isfinite(outs[backend])), f"{backend} finite")
        say("solver", backend=backend, first_s=first, warm_s=warm,
            compile_s=first - warm, solves_per_s=batch * tiles / warm,
            residual=float(np.max(np.asarray(sol.residual))))
    check_compiled()
    for backend in ("pallas", "fused"):
        err = np.max(np.abs(outs[backend] - outs["scan"])) / np.max(
            np.abs(outs["scan"])
        )
        say("solver", compare=f"{backend}_vs_scan", max_rel_err=float(err))
        np.testing.assert_allclose(
            outs[backend], outs["scan"], rtol=RTOL_BACKENDS, atol=0
        )

    g_np, v_np = np.asarray(g), np.asarray(v)
    worst = 0.0
    for s, t in ((0, 0), (batch // 2, tiles // 3), (batch - 1, tiles - 1)):
        want = dense_mna_i_out(g_np[t], v_np[s, t], cp)
        for backend, out in outs.items():
            np.testing.assert_allclose(
                out[s, t], want, rtol=RTOL_BACKENDS, atol=0,
                err_msg=f"{backend} vs dense MNA, sample {s} tile {t}",
            )
            worst = max(worst, float(np.max(np.abs(out[s, t] - want)
                                            / np.max(np.abs(want)))))
    say("solver", compare="all_vs_dense_mna_f64", tiles_checked=3,
        max_rel_err=worst)

    # Ideal crossbar: one dot per tile, against float64 on the host.
    ideal = np.asarray(jax.jit(solve_ideal)(g[None], v))
    want = np.einsum("tmn,btm->btn", g_np.astype(np.float64), v_np)
    err = float(np.max(np.abs(ideal - want)) / np.max(np.abs(want)))
    say("solver", compare="ideal_vs_numpy_f64", max_rel_err=err)
    np.testing.assert_allclose(ideal, want, rtol=1e-5, atol=0)

    # Table III's top row: one big tile through "fused".
    gb = jax.random.uniform(
        key_big, (big, big), minval=MRAM.g_off, maxval=MRAM.g_on
    )
    vb = jax.random.uniform(key_v, (big,), maxval=0.8)
    cpb = CircuitParams(gs_iters=suggest_iters(big, big))
    big_outs = {}
    for backend in ("scan", "fused"):
        n_events = len(obs.events("backend_fallback"))
        options = SolveOptions(backend=backend)
        fn = jax.jit(
            lambda g, v, o=options: solve_crossbar(g, v, cpb, options=o).i_out
        )
        out, first, warm = timed(fn, gb, vb)
        big_outs[backend] = np.asarray(out)
        fallbacks = obs.events("backend_fallback")[n_events:]
        ran = fallbacks[-1]["fields"]["to_backend"] if fallbacks else backend
        say("solver", tile=f"{big}x{big}", requested=backend, ran=ran,
            sweeps=cpb.gs_iters, first_s=first, warm_s=warm)
        if backend == "fused":
            expect = "pallas" if fused_lane_block(big, big) < 1 else "fused"
            require(ran == expect, f"{big}x{big} ran {ran}, expected {expect}")
    check_compiled()
    err = float(np.max(np.abs(big_outs["fused"] - big_outs["scan"]))
                / np.max(np.abs(big_outs["scan"])))
    say("solver", compare=f"{big}x{big}_fused_vs_scan", max_rel_err=err)
    np.testing.assert_allclose(
        big_outs["fused"], big_outs["scan"], rtol=RTOL_BACKENDS, atol=0
    )
    say("solver", phase_s=time.perf_counter() - t_phase)


def train(n_train=4000, n_test=500, steps=500):
    """The paper's MLP, trained from SEED on the synthetic digit set."""
    import jax

    from repro.configs.imac_mnist import TOPOLOGY
    from repro.core.digital import accuracy, train_mlp
    from repro.data.digits import train_test_split

    t0 = time.perf_counter()
    xtr, ytr, xte, yte = train_test_split(n_train, n_test, seed=SEED,
                                          noise=0.4)
    params = train_mlp(jax.random.PRNGKey(SEED), TOPOLOGY, xtr, ytr,
                       steps=steps)
    acc = accuracy(params, xte, yte)
    say("train", topology="-".join(map(str, TOPOLOGY)), steps=steps,
        digital_accuracy=acc, wall_s=time.perf_counter() - t0)
    require(acc > 0.9, f"digital MLP failed to train: {acc}")
    return params, xte, yte


def pipeline_phase(params, xte, yte, n_samples=256) -> None:
    from repro.configs.imac_mnist import TABLE_III_CONFIGS
    from repro.core.evaluate import evaluate_batch
    from repro.core.solver import SolveOptions

    cfg = dict(TABLE_III_CONFIGS)["32x32"]
    results = {}
    for backend in ("scan", "fused"):
        t0 = time.perf_counter()
        (res,) = evaluate_batch(
            params, xte, yte, [cfg], n_samples=n_samples,
            solve_options=SolveOptions(backend=backend),
        )
        results[backend] = res
        say("pipeline", backend=backend, tech="MRAM", array="32x32",
            samples=res.n_samples, accuracy=res.accuracy,
            digital_accuracy=res.digital_accuracy,
            hp=list(res.hp), vp=list(res.vp), power_w=res.avg_power,
            latency_ns=res.latency * 1e9, worst_residual=res.worst_residual,
            wall_s=time.perf_counter() - t0)
        require(res.hp == (13, 4, 3) and res.vp == (4, 3, 1),
                f"H_P {res.hp} / V_P {res.vp}")
        require(_finite(res.avg_power, res.latency, res.worst_residual),
                f"{backend} results finite")
    check_compiled()
    a, b = results["scan"], results["fused"]
    power_err = abs(a.avg_power - b.avg_power) / abs(a.avg_power)
    sample_diff = round(abs(a.accuracy - b.accuracy) * n_samples)
    say("pipeline", compare="fused_vs_scan", power_rel_err=power_err,
        accuracy_diff_samples=sample_diff)
    require(power_err <= RTOL_BACKENDS, f"power differs by {power_err}")
    require(sample_diff <= 1, f"accuracy differs by {sample_diff} samples")


def table_grid():
    """Table III partitioning x Table IV technology: 24 design points."""
    from repro.configs.imac_mnist import TABLE_III_CONFIGS, TABLE_IV_CONFIGS

    return [
        (f"{part}/{tech}", dataclasses.replace(cfg, tech=tech))
        for part, cfg in TABLE_III_CONFIGS
        for tech, _ in TABLE_IV_CONFIGS
    ]


def _finite(*values) -> bool:
    return bool(np.all(np.isfinite(np.asarray(values, np.float64))))


def engines_phase(params, xte, yte, n_samples=64, trials=8, n_steps=24):
    from repro.configs.imac_mnist import TABLE_III_CONFIGS, TABLE_IV_CONFIGS
    from repro.explore import run_sweep
    from repro.transient import TransientSpec, run_transient
    from repro.variability import VariabilitySpec, run_variability

    grid = table_grid()
    t0 = time.perf_counter()
    sweep = run_sweep(params, xte, yte, grid, n_samples=n_samples,
                      chunk=n_samples, cache=None)
    say("engines", engine="run_sweep", points=len(sweep),
        wall_s=time.perf_counter() - t0)
    require([r.name for r in sweep] == [name for name, _ in grid],
            "sweep results in point order")
    for r in sweep:
        res = r.result
        require(0.0 <= res.accuracy <= 1.0
                and _finite(res.avg_power, res.latency, res.worst_residual),
                f"{r.name} results finite")
        say("engines", point=r.name, accuracy=res.accuracy,
            power_w=res.avg_power, latency_ns=res.latency * 1e9)

    cfg = dict(TABLE_III_CONFIGS)["32x32"]
    spec = VariabilitySpec(trials=trials, seed=SEED, sigma_rel=0.05,
                           p_stuck_off=0.001)
    t0 = time.perf_counter()
    rep = run_variability(params, xte, yte, cfg, spec, n_samples=n_samples,
                          chunk=n_samples)
    say("engines", engine="run_variability", trials=rep.n_trials,
        acc_mean=rep.acc_mean, acc_min=rep.acc_min,
        power_worst_w=rep.power_worst, yield_frac=rep.yield_frac,
        wall_s=time.perf_counter() - t0)
    require(rep.n_trials == trials and len(rep.per_trial_accuracy) == trials,
            f"{rep.n_trials} trials reported")
    require(_finite(rep.acc_mean, rep.power_mean, rep.power_worst,
                    rep.latency, *rep.per_trial_power),
            "variability results finite")

    cfgs = [cfg for _, cfg in TABLE_IV_CONFIGS]
    tspec = TransientSpec(t_stop=20e-9, n_steps=n_steps, gs_iters=4,
                          n_probe=1)
    t0 = time.perf_counter()
    tr = run_transient(params, cfgs, xte, spec=tspec)
    latency = np.asarray(tr.latency)
    energy = np.asarray(tr.energy)
    say("engines", engine="run_transient", configs=len(cfgs),
        steps=n_steps, latency_ns=[round(float(x) * 1e9, 3) for x in latency],
        energy_nj=[round(float(x) * 1e9, 4) for x in energy],
        wall_s=time.perf_counter() - t0)
    require(latency.shape == energy.shape == (len(cfgs),),
            f"transient shapes {latency.shape} {energy.shape}")
    require(_finite(*latency, *energy) and np.all(latency > 0),
            "transient latency and energy finite")


def shard_phase(params, xte, yte, n_samples=64, devices=4) -> None:
    """Table IV's technology group (4 points) plus Table III's 128x128
    row in three technologies (a group of 3, padded to the mesh)."""
    from repro.configs.imac_mnist import TABLE_III_CONFIGS, TABLE_IV_CONFIGS
    from repro.distributed.sweep import MeshPlan
    from repro.explore import run_sweep

    row = dict(TABLE_III_CONFIGS)["128x128"]
    points = [(f"tableIV/{t}", c) for t, c in TABLE_IV_CONFIGS] + [
        (f"128x128/{t}", dataclasses.replace(row, tech=t))
        for t in ("MRAM", "RRAM", "PCM")
    ]
    runs = {}
    for label, shard in (("sharded", MeshPlan(devices=devices)),
                         ("unsharded", None)):
        t0 = time.perf_counter()
        runs[label] = run_sweep(params, xte, yte, points,
                                n_samples=n_samples, chunk=n_samples,
                                cache=None, shard=shard)
        say("shard", run=label, points=len(points),
            devices=devices if shard else 1,
            wall_s=time.perf_counter() - t0)
    worst_power, identical = 0.0, 0
    for a, b in zip(runs["sharded"], runs["unsharded"]):
        ra, rb = a.result, b.result
        require(a.name == b.name, f"point order {a.name} {b.name}")
        require(ra.accuracy == rb.accuracy and ra.error_rate == rb.error_rate,
                f"{a.name} accuracy {ra.accuracy} vs {rb.accuracy}")
        err = abs(ra.avg_power - rb.avg_power) / abs(rb.avg_power)
        worst_power = max(worst_power, err)
        identical += ra == rb
        say("shard", point=a.name, accuracy=ra.accuracy,
            power_rel_err=err, bitwise_equal=ra == rb)
    say("shard", compare="sharded_vs_unsharded", points=len(points),
        accuracy_equal=len(points), bitwise_equal_results=identical,
        max_power_rel_err=worst_power)
    require(worst_power <= RTOL_SHARD_POWER, f"power differs by {worst_power}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-device sharded sweep and its "
                    "unsharded comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {dev.platform!r});"
              " this check runs only on a TPU", file=sys.stderr)
        return 1
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(devices), jax=jax.__version__)

    import repro

    if ROOT / "src" not in Path(repro.__file__).resolve().parents:
        print(f"chip_smoke: repro imported from {repro.__file__}, not from "
              f"{ROOT / 'src'}", file=sys.stderr)
        return 1
    from repro import obs
    from repro.compile_cache import enable_compile_cache

    say("setup", compile_cache=enable_compile_cache())
    obs.enable()

    t0 = time.perf_counter()
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        params, xte, yte = train()
        shard_phase(params, xte, yte)
    else:
        solver_phase()
        params, xte, yte = train()
        pipeline_phase(params, xte, yte)
        engines_phase(params, xte, yte)
    # dryrun fakes 512 host devices through XLA_FLAGS when imported.
    require("repro.launch.dryrun" not in sys.modules,
            "the chip path imported repro.launch.dryrun")
    say("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
