#!/usr/bin/env python3
"""Readings that the comparison limits of a cell are set from.

    python bench/control.py --workload <cell> --seeds 1,2,3 --controls 7,8,9

For each seed of `--seeds` it runs the cell's timed path (the program as
the window drives it: the same calls at the cell's sizes, as many per
seed as a run checks) and prints the numbers the check compares: the
lower readings. For each seed of `--controls` it runs each of the kind's
controls (`CONTROLS` of `bench/kinds/<kind>.py`: a lower-precision or
otherwise broken computation in the program's place) and prints the same
numbers: the upper readings.

Each reading is one line `reading <kind> seed=<s> <number>=<value> ...`;
the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run  # noqa: E402  (bench/run.py: cell and kind lookup, program import)


def readings(kind, spec, seed, name, n_calls, control=None):
    traffic, _ = kind.build(spec["config"], spec["mix"], seed, control=control)
    calls = [c for _ in range(n_calls) for c in traffic.window(0, traffic.label)[0]]
    t0 = time.perf_counter()
    worst = kind.compare(traffic, calls, seed)
    text = " ".join(f"{k}={v}" for k, v in worst.items() if k != "per_point")
    for point, nums in worst["per_point"]:
        print(f"  point {name} seed={seed} {point} {nums}", flush=True)
    print(f"reading {name} seed={seed} {text} reference_s={time.perf_counter() - t0}",
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

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["cell"]["chips"]:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 3
    run.import_program()
    run.use_compile_cache()
    kind = run.load_kind(spec["mix"]["kind"])
    calls = args.calls or int(spec["mix"].get("check_calls", 1))
    for s in filter(None, args.seeds.split(",")):
        readings(kind, spec, int(s), "program", calls)
    for s in filter(None, args.controls.split(",")):
        for name in kind.CONTROLS:
            readings(kind, spec, int(s), name, calls, control=name)
    print(json.dumps({"workload": args.workload, "done": True}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
