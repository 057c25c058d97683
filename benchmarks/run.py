"""Benchmark harness: one function per paper table + system benches.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit)
and, per selected bench, writes the same rows plus run metadata to
``BENCH_<name>.json`` in the repo root (machine-readable trend input).

  table3      — paper Table III (partitioning design space)
  table4      — paper Table IV (device technologies)
  sweep       — batched exploration engine vs per-config loop (Table III x IV)
  variability — batched Monte-Carlo reliability engine vs per-trial loop
  transient   — batched transient co-simulation vs per-config loop + the
                analytic-vs-waveform settling crossvalidation
  solver      — crossbar circuit-solver scaling (the adapted SPICE engine)
  kernels     — Pallas kernel workloads (ref-path timings on CPU)
  deploy      — IMAC deployment planning for the 10 assigned archs
  roofline    — (arch x shape x mesh) roofline table from dry-run artifacts

Observability (repro.obs): ``--trace FILE`` enables the tracer and
writes a Chrome trace_event JSON (load in chrome://tracing or Perfetto);
``--metrics-out FILE`` enables metrics and writes the Prometheus text
exposition. Either flag also embeds the metrics snapshot in each
``BENCH_<name>.json``.

Performance trajectory (repro.obs.ledger): every invocation appends one
JSONL entry per selected bench — run id, git SHA + dirty flag,
jax/device metadata, the timing rows, the metrics snapshot — to the
run ledger (``--ledger PATH``, default ``$REPRO_OBS_LEDGER`` or
``artifacts/perf_ledger.jsonl``; ``--no-ledger`` to skip). The ledger
is the durable perf store the one-shot BENCH files never were: gate it
with ``python -m benchmarks.regress`` and render it with
``python -m repro.obs.report``.

Deep profiling (repro.obs.prof): ``--jax-profile DIR`` (or
``REPRO_OBS_JAX_PROFILE``) captures a jax.profiler device trace of the
whole run; ``--cost`` (or ``REPRO_OBS_COST=1``) records per-jitted-fn
HLO cost analysis (hlo_flops / hlo_bytes_accessed gauges).

Usage: PYTHONPATH=src python -m benchmarks.run [--only table3,table4,...]
           [--trace trace.json] [--metrics-out metrics.prom]
           [--ledger ledger.jsonl | --no-ledger] [--jax-profile DIR]
           [--cost]
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys
import traceback

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

#: BENCH_<name>.json schema: bumped when the payload layout changes.
#: v2 adds run metadata (git SHA, jax/device/python) and the optional
#: embedded repro.obs metrics snapshot.
SCHEMA_VERSION = 2


def _git_sha() -> str:
    try:
        return (
            subprocess.run(
                ["git", "rev-parse", "HEAD"],
                cwd=REPO_ROOT,
                capture_output=True,
                text=True,
                timeout=10,
            ).stdout.strip()
            or "unknown"
        )
    except Exception:
        return "unknown"


def _git_dirty() -> "bool | None":
    try:
        proc = subprocess.run(
            ["git", "status", "--porcelain"],
            cwd=REPO_ROOT,
            capture_output=True,
            text=True,
            timeout=10,
        )
        return bool(proc.stdout.strip()) if proc.returncode == 0 else None
    except Exception:
        return None


def _metadata() -> dict:
    import jax

    from repro.obs import ledger

    devices = jax.devices()
    return {
        "schema_version": SCHEMA_VERSION,
        "git_sha": _git_sha(),
        "git_dirty": _git_dirty(),
        "jax_version": jax.__version__,
        "jax_backend": jax.default_backend(),
        "device_platform": devices[0].platform if devices else "none",
        "device_kind": devices[0].device_kind if devices else "none",
        "device_count": len(devices),
        # "data8" under a sharded-bench process ($REPRO_MESH_SHAPE or an
        # engine mesh_context); None on single-device runs. Part of the
        # regress env-matching key so sharded timings never gate
        # single-device baselines.
        "mesh_shape": ledger.current_mesh_context(),
        "python_version": platform.python_version(),
    }


def _write_json(name: str, rows, ok: bool, meta: dict) -> None:
    """Snapshot one bench's emitted rows as BENCH_<name>.json."""
    from repro import obs

    payload = {
        "bench": name,
        "ok": ok,
        **meta,
        "rows": [
            {"name": n, "us_per_call": us, "derived": derived}
            for n, us, derived in rows
        ],
    }
    if obs.enabled():
        payload["metrics"] = obs.snapshot()
    path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset")
    ap.add_argument(
        "--trace",
        default=None,
        metavar="FILE",
        help="enable repro.obs and write a Chrome trace_event JSON",
    )
    ap.add_argument(
        "--metrics-out",
        default=None,
        metavar="FILE",
        help="enable repro.obs and write a Prometheus text exposition",
    )
    ap.add_argument(
        "--ledger",
        default=None,
        metavar="FILE",
        help="perf-ledger path (default: $REPRO_OBS_LEDGER or "
        "artifacts/perf_ledger.jsonl)",
    )
    ap.add_argument(
        "--no-ledger",
        action="store_true",
        help="do not append this run to the perf ledger",
    )
    ap.add_argument(
        "--jax-profile",
        default=None,
        metavar="DIR",
        help="capture a jax.profiler trace of the run into DIR "
        "(also: REPRO_OBS_JAX_PROFILE)",
    )
    ap.add_argument(
        "--cost",
        action="store_true",
        help="enable per-jitted-fn HLO cost analysis "
        "(also: REPRO_OBS_COST=1)",
    )
    args = ap.parse_args()

    from repro import obs
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.trace or args.metrics_out:
        obs.enable()
    if args.cost:
        obs.prof.enable_cost()

    from benchmarks import (
        deploy_report,
        kernels_bench,
        roofline_report,
        solver_scaling,
        sweep_bench,
        table3_partitioning,
        table4_device_tech,
        transient_bench,
        variability_bench,
    )

    benches = {
        "table3": table3_partitioning.run,
        "table4": table4_device_tech.run,
        "sweep": sweep_bench.run,
        "variability": variability_bench.run,
        "transient": transient_bench.run,
        "solver": solver_scaling.run,
        "kernels": kernels_bench.run,
        "deploy": deploy_report.run,
        "roofline": roofline_report.run,
    }
    selected = (
        [s.strip() for s in args.only.split(",")] if args.only else list(benches)
    )
    from benchmarks import common

    meta = _metadata()
    ledger_path = None if args.no_ledger else (
        args.ledger or obs.ledger.default_path()
    )

    def _record(name: str, rows, ok: bool) -> None:
        _write_json(name, rows, ok=ok, meta=meta)
        if ledger_path is None:
            return
        try:
            obs.ledger.record_run(
                name,
                rows,
                ok=ok,
                meta={k: v for k, v in meta.items()},
                metrics=obs.snapshot() if obs.enabled() else None,
                path=ledger_path,
            )
        except Exception as e:  # a broken ledger must not fail the bench
            print(f"# ledger append failed: {e!r}", file=sys.stderr)

    print("name,us_per_call,derived")
    failures = []
    with obs.prof.jax_profile(args.jax_profile):
        for name in selected:
            start = len(common.CSV_ROWS)
            try:
                with obs.trace(f"bench[{name}]"):
                    benches[name]()
                _record(name, common.CSV_ROWS[start:], ok=True)
            except Exception as e:  # keep the harness going; report at exit
                traceback.print_exc()
                failures.append((name, repr(e)))
                _record(name, common.CSV_ROWS[start:], ok=False)
    if ledger_path is not None:
        print(f"# ledger appended: {ledger_path}", file=sys.stderr)
    if args.trace:
        obs.export_chrome_trace(args.trace)
        print(f"# trace written to {args.trace}", file=sys.stderr)
    if args.metrics_out:
        obs.export_prometheus_file(args.metrics_out)
        print(f"# metrics written to {args.metrics_out}", file=sys.stderr)
    if failures:
        print(f"FAILED benches: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
