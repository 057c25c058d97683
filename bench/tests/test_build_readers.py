"""The readers of the program's trace counter, device wait and trial
sampling, on a synthetic run context."""
import types

import pytest

import run

NEW = ("jit_traces_per_point.batch", "jit_traces_per_point.loop",
       "device_wait_ms_per_point.batch", "trial_sampling_ms_per_trial")


def span(sid, name, parent, t0, t1):
    return types.SimpleNamespace(sid=sid, name=name, parent=parent,
                                 t_start=t0, t_end=t1, duration=t1 - t0)


def ctx(spans, snapshot, points=4, registered=None):
    """Two calls of `points` points; `registered`: the metric names the
    program registered in set-up and window (default: the window's)."""
    call = types.SimpleNamespace(points=[object()] * points)
    if registered is None:
        registered = set(snapshot)
    return types.SimpleNamespace(spans=spans, snapshot=snapshot,
                                 registered=registered, calls=[call, call])


def traced_run():
    # One call: solve_chunk holds an outer trace with two nested traces,
    # a lowering and a fetch; device_wait follows the solve.
    spans = [
        span(1, "evaluate_batch", None, 0.0, 10.0),
        span(2, "solve", 1, 0.0, 6.0),
        span(3, "solve_chunk[compile]", 2, 0.0, 5.5),
        span(4, "jit_trace", 3, 0.5, 2.0),
        span(5, "jit_trace", 4, 0.6, 0.8),
        span(6, "jit_trace", 4, 1.0, 1.1),
        span(7, "jit_lower", 3, 2.0, 4.0),
        span(8, "jit_trace", 7, 2.5, 2.7),
        span(9, "executable_fetch", 3, 4.0, 5.0),
        span(10, "device_wait", 1, 6.0, 8.0),
        span(11, "measure", 1, 8.0, 10.0),
    ]
    snapshot = {"jit_traces_total": {"type": "counter",
                                     "series": [{"labels": {}, "value": 4.0}]}}
    return ctx(spans, snapshot)


@pytest.mark.parametrize("name", ["jit_traces_per_point.batch",
                                  "jit_traces_per_point.loop"])
def test_traces_per_point_from_the_counter(name):
    assert run.reader(name)(traced_run()) == pytest.approx(4.0 / 8)


@pytest.mark.parametrize("name", ["jit_traces_per_point.batch",
                                  "jit_traces_per_point.loop"])
def test_a_warm_window_reads_zero_traces(name):
    # The program registered the counter in set-up and traced nothing in
    # the window: the snapshot taken after the window has no series.
    spans = [span(1, "evaluate_batch", None, 0.0, 10.0)]
    warm = ctx(spans, {}, registered={"jit_traces_total", "solver_sweeps"})
    assert run.reader(name)(warm) == 0.0


def test_trial_sampling_is_self_time_per_trial():
    # Two studies of 4 trials; sample_trials holds a nested build span.
    spans = [span(1, "run_variability", None, 0.0, 10.0),
             span(2, "sample_trials", 1, 0.0, 2.0),
             span(3, "jit_trace", 2, 0.5, 1.0),
             span(4, "run_variability", None, 10.0, 20.0),
             span(5, "sample_trials", 4, 10.0, 11.0)]
    assert run.reader("trial_sampling_ms_per_trial")(ctx(spans, {})) == (
        pytest.approx(1e3 * 2.5 / 8))


def test_device_wait_per_point():
    assert run.reader("device_wait_ms_per_point.batch")(traced_run()) == (
        pytest.approx(1e3 * 2.0 / 8))


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_them_reads_none(name):
    # What the parent program records: no build spans, no device_wait,
    # no trace counter; and an untraced run records nothing at all.
    spans = [span(1, "evaluate_batch", None, 0.0, 10.0),
             span(2, "solve_chunk[compile]", 1, 0.0, 9.0)]
    solver = {"solver_sweeps": {"type": "histogram", "series": []}}
    assert run.reader(name)(ctx(spans, solver)) is None
    assert run.reader(name)(ctx([], {})) is None


def test_every_new_metric_is_declared_for_its_cells():
    import json

    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:
        cells = declared[name]["workloads"]
        assert cells
        for cell in cells:
            assert name in [m["name"] for m in run.load_cell(cell)["per_layer"]]
