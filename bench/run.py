#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in BENCHMARK.json at the root of the
checkout. Its configuration (`bench/configs/<config>.json`), traffic mix
(`bench/traffic/<traffic>.json`), comparison limits
(`bench/limits/<cell>.json`) and the reader of each metric
(`bench/metrics/<metric>.py`) are found by name, and so is the mix's
kind (`bench/kinds/<kind>.py`, from the mix's `kind`). A kind module
provides:

  build(cfg, mix, seed, control=None)
            -> (traffic, notes): the run's inputs and weights made from
               the seed, and the object that drives the program's calls;
               `control` names one of CONTROLS, a lower-precision or
               otherwise broken computation in the program's place (for
               `bench/control.py` only); `notes` is logged with set-up
  CONTROLS  the control names `build` takes
  NUMBERS   the names of the numbers `compare` returns, each with a limit
  compare(traffic, calls, seed)
            -> {number: worst value, "checked_points": n, "per_point": [...]}
  failed(calls)
            -> the count of results that are not finite

The traffic has `label` (the profiler annotation around each call),
`n_samples` (inputs per evaluation unit), `parasitics`, `warm_up()`,
`window(seconds, label, on_call=None)` -> (calls, window_s) and
`inputs(call)`. Each call has `points` (one entry per evaluation unit:
a design point, or a Monte-Carlo trial, with its structure `group` and
`partitioning`), `t_sent`, `t_done` and `latency_s`.

Set-up makes the inputs and weights from the seed on the device, then
warms up every program the traffic uses. The window then runs the
traffic for `--seconds` (whole calls only). With `--trace 1` the window
runs with the program's spans on and under the JAX profiler, and the
result carries the cell's per-layer metrics; with `--trace 0` it carries
the end-to-end metrics. After the window the kind compares a sample of
its results with the float64 reference. The last line of standard
output is one JSON object; the numbers compared, with their limits, end
it and end standard error.

Without a TPU, or with fewer chips than the cell needs, it prints no
result and exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
KINDS = BENCH / "kinds"
sys.path.insert(0, str(BENCH))

#: Program spans whose self time is host work of the engine and evaluation.
HOST_SPANS = ("memo_lookup", "prepare", "map", "stamp", "shard_stage", "measure")


def load_cell(workload: str) -> dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]

    def read(kind, name):
        return json.loads((BENCH / kind / f"{name}.json").read_text())

    # An end-to-end metric without a cell list is reported by every cell;
    # every per-layer metric lists its cells.
    end_to_end = [m for m in spec["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    per_layer = [m for m in spec["per_layer"] if workload in m["workloads"]]
    return {
        "cell": cell,
        "config": read("configs", cell["config"]),
        "mix": read("traffic", cell["traffic"]),
        "limits": read("limits", workload),
        "end_to_end": end_to_end,
        "per_layer": per_layer,
    }


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py", f"metric_{name}").read


def load_kind(name: str):
    """The kind module of a traffic mix, `bench/kinds/<name>.py`."""
    path = KINDS / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in KINDS.glob("*.py"))
        raise SystemExit(f"unknown traffic kind {name!r} (no {path}); known: {known}")
    return load_module(path, f"kind_{name}")


def verdict(worst: dict, limits: dict, numbers) -> "tuple[bool, dict]":
    """(correct, {number: {value, limit}}): every number within its limit,
    and something was checked."""
    shown = {k: {"value": worst[k], "limit": limits[k]} for k in numbers}
    ok = worst.get("checked_points", 0) > 0 and all(
        v["value"] <= v["limit"] for v in shown.values())
    return ok, shown


def compile_counter():
    """Counts of backend compiles and persistent-cache hits, by JAX events."""
    from jax import monitoring

    counts = collections.Counter()

    def on_duration(name, *_args, **_kw):
        if name in ("/jax/core/compile/backend_compile_duration",
                    "/jax/compilation_cache/cache_retrieval_time_sec"):
            counts[name.rsplit("/", 1)[-1]] += 1

    def on_event(name, *_args, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return counts


def use_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(BENCH / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # Every program, however quick to compile, so that no call of the
    # window compiles what an earlier process already has.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def import_program():
    sys.path.insert(0, str(ROOT / "src"))
    import repro

    where = Path(repro.__file__).resolve()
    if ROOT / "src" not in where.parents:
        raise SystemExit(f"repro imported from {where}, not from {ROOT / 'src'}")


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    cell, cfg, mix = spec["cell"], spec["config"], spec["mix"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        say(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
            f"JAX found {len(devices)} {devices[0].platform} device(s)")
        return 3
    return execute(args, spec, devices)


def execute(args, spec, devices) -> int:
    """Everything after the look for chips: set-up, window, metrics, check."""
    import jax

    cell, cfg, mix = spec["cell"], spec["config"], spec["mix"]
    import_program()
    kind = load_kind(mix["kind"])
    cache_dir = use_compile_cache()
    compiles = compile_counter()

    from benchlib import tracing

    traffic, notes = kind.build(cfg, mix, args.seed)
    if args.trace:
        from repro import obs

        obs.enable()  # spans and counters run their own small programs
    traffic.warm_up()
    setup_s = time.perf_counter() - T_START
    before = dict(compiles)
    say(f"bench: setup_s={setup_s} {' '.join(f'{k}={v}' for k, v in notes.items())} "
        f"compile_cache={cache_dir} compiles_in_setup={before}")

    spans, snapshot, host_marks, registered = [], {}, [], set()
    if args.trace:
        logdir = BENCH / ".out" / "trace"
        shutil.rmtree(logdir, ignore_errors=True)
        # The metrics the program registered in set-up: a counter that the
        # window leaves at 0 reads 0, not "absent", where the program has it.
        registered = set(obs.snapshot())
        obs.reset()
        seconds = min(args.seconds, float(mix.get("trace_seconds", args.seconds)))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the Python tracer slows the host severalfold
        with jax.profiler.trace(str(logdir), profiler_options=options):
            calls, window_s = traffic.window(
                seconds, traffic.label,
                on_call=lambda c: host_marks.append((traffic.label, c.t_sent * 1e9)))
        spans, snapshot = obs.spans(), obs.snapshot()
        obs.disable()
        trace = tracing.Trace.load(tracing.find_xplane(str(logdir)),
                                   labels=(traffic.label,))
        offset = trace.clock_offset(host_marks)
        summary = trace.summary(tracing.spans_on_trace(spans, offset))
        shutil.rmtree(logdir, ignore_errors=True)
    else:
        calls, window_s = traffic.window(args.seconds, traffic.label)
        summary = None
    in_window = {k: v - before.get(k, 0) for k, v in compiles.items()}
    n_compiled = in_window.get("backend_compile_duration", 0) - in_window.get("cache_hits", 0)
    print(f"bench: compiles_in_window={n_compiled} events={in_window}", flush=True)

    used = devices[: cell["chips"]]
    stats = [d.memory_stats() or {} for d in used]
    print(f"bench: memory_stats={stats}", flush=True)
    # The allocator counts program temporaries as reserved, not in use.
    peak = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)

    ctx = types.SimpleNamespace(
        config=cfg, traffic=traffic, calls=calls, window_s=window_s,
        setup_s=setup_s, trace=summary, spans=spans, snapshot=snapshot,
        registered=registered | set(snapshot), host_spans=HOST_SPANS,
        device_kind=devices[0].device_kind, chips=cell["chips"],
    )
    wanted = spec["per_layer"] if args.trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    attempted = sum(len(c.points) for c in calls)
    t_check = time.perf_counter()
    worst = kind.compare(traffic, calls, args.seed)
    check_s = time.perf_counter() - t_check
    correct, shown = verdict(worst, spec["limits"], kind.NUMBERS)
    failed = kind.failed(calls)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": [list(x) for x in summary["device_ops"]],
                               "idle_gaps": [list(x) for x in summary["idle_gaps"]]}
    result["checks"] = shown
    say(f"bench: calls={len(calls)} window_s={window_s} "
        f"checked_points={worst['checked_points']} check_s={check_s}")
    for name, v in shown.items():
        say(f"check {name} {v['value']} limit {v['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
