#!/usr/bin/env python3
"""Record the small chip trace that `test_tracing.py` reduces.

    python bench/tests/record_trace_fixture.py   # on a TPU

Two ideal-crossbar requests of `grid-screen-loop`'s traffic, at 16
inputs, under the JAX profiler with the Python tracer off, each wrapped
in the harness's `request` annotation. Writes
`bench/tests/fixtures/loop_trace.xplane.pb`.
"""
from __future__ import annotations

import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import run  # noqa: E402


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_trace_fixture: needs a TPU", file=sys.stderr)
        return 3
    run.import_program()
    run.use_compile_cache()
    from benchlib import data, tracing
    from benchlib.traffic import Traffic

    spec = run.load_cell("grid-screen-loop")
    cfg, mix = spec["config"], dict(spec["mix"], n_samples=16, chunk=16)
    params, x_pool, y_pool, _ = data.make_workload(11, cfg)
    traffic = Traffic(cfg, mix, 11, params, x_pool, y_pool)
    traffic.warm_up()
    logdir = run.BENCH / ".out" / "fixture"
    shutil.rmtree(logdir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(logdir), profiler_options=options):
        for _ in range(2):
            traffic.call(traffic.next_points(), "request")
    out = HERE / "fixtures" / "loop_trace.xplane.pb"
    out.parent.mkdir(exist_ok=True)
    shutil.copy(tracing.find_xplane(str(logdir)), out)
    print(tracing.Trace.load(str(out), labels=("request",)).summary())
    shutil.rmtree(logdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
