"""The readers of the program's group-program cache counter, on a synthetic
run context."""
import types

import pytest

import run

NEW = ("program_cache_hit_pct.batch", "program_cache_hit_pct.loop")


def ctx(snapshot, points=4):
    call = types.SimpleNamespace(points=[object()] * points)
    return types.SimpleNamespace(spans=[], snapshot=snapshot, calls=[call, call])


def lookups(**values):
    series = [{"labels": {"result": k}, "value": float(v)}
              for k, v in values.items()]
    return {"program_cache_lookups_total": {"type": "counter", "series": series}}


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("values, share", [
    ({"hit": 6}, 100.0),
    ({"hit": 3, "miss": 1}, 75.0),
    ({"hit": 1, "miss": 2, "bypass": 1}, 25.0),
    ({"miss": 2, "bypass": 2}, 0.0),
])
def test_hit_share_of_all_lookups(name, values, share):
    assert run.reader(name)(ctx(lookups(**values))) == pytest.approx(share)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counter_reads_none(name):
    # What the parent program records: other counters, and an untraced
    # run records nothing at all.
    other = {"jit_traces_total": {"type": "counter",
                                  "series": [{"labels": {}, "value": 4.0}]}}
    assert run.reader(name)(ctx(other)) is None
    assert run.reader(name)(ctx({})) is None


@pytest.mark.parametrize("name", NEW)
def test_no_lookups_never_divides(name):
    assert run.reader(name)(ctx(lookups(hit=0, miss=0, bypass=0))) is None
    assert run.reader(name)(ctx(lookups())) is None


def test_every_new_metric_is_declared_for_its_cells():
    import json

    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:
        assert declared[name]["layer"] == "program build (trace, lower, fetch)"
        for cell in declared[name]["workloads"]:
            assert name in [m["name"] for m in run.load_cell(cell)["per_layer"]]
