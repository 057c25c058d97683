"""Tests for the continuous-performance tier: repro.obs.ledger /
regress / prof / report and the benchmarks.regress CLI gate."""
import json
import math

import pytest

from repro import obs
from repro.obs import ledger, prof, regress, report


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _meta(**over):
    meta = {
        "git_sha": "abc123def456",
        "git_dirty": False,
        "python_version": "3.10.16",
        "jax_version": "0.4.37",
        "jax_backend": "cpu",
        "device_platform": "cpu",
        "device_count": 1,
    }
    meta.update(over)
    return meta


def _entry(us, bench="solver", row="solver/gs_8x8", ts=0.0, **over):
    e = ledger.make_entry(
        bench,
        [{"name": row, "us_per_call": us, "derived": "iters=48"}],
        meta=_meta(**over),
    )
    e["ts_unix"] = ts
    return e


# ---------------------------------------------------------------------------
# Ledger.
# ---------------------------------------------------------------------------


def test_ledger_append_load_round_trip(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    e1 = _entry(100.0, ts=1.0)
    e2 = _entry(110.0, ts=2.0)
    ledger.append(e1, path)
    ledger.append(e2, path)
    loaded = ledger.load(path)
    assert [x["run_id"] for x in loaded] == [e1["run_id"], e2["run_id"]]
    assert loaded[0]["rows"] == [
        {"name": "solver/gs_8x8", "us_per_call": 100.0, "derived": "iters=48"}
    ]
    assert loaded[0]["git_sha"] == "abc123def456"
    assert loaded[0]["schema"] == ledger.ENTRY_SCHEMA


def test_ledger_skips_corrupt_lines(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    ledger.append(_entry(100.0), path)
    with open(path, "a") as fh:
        fh.write('{"truncated": \n')
        fh.write("not json at all\n")
        fh.write('{"valid_json": "but not an entry"}\n')
    ledger.append(_entry(101.0), path)
    entries, skipped = ledger.load_report(path)
    assert len(entries) == 2
    assert skipped == 3


def test_ledger_load_missing_file_is_empty(tmp_path):
    assert ledger.load(str(tmp_path / "nope.jsonl")) == []


def test_ledger_entry_metadata_is_gathered_when_absent():
    e = ledger.make_entry("x", [("r", 1.0, "")])
    assert e["git_sha"]  # repo checkout: a real sha (or "unknown")
    assert "git_dirty" in e
    assert e["jax_backend"] != ""
    assert e["bench"] == "x"
    # One JSONL line, parseable.
    assert json.loads(json.dumps(e))["rows"][0]["name"] == "r"


def test_ledger_matching_filters_env_and_bench():
    cpu = _entry(100.0, ts=1.0)
    tpu = _entry(50.0, ts=2.0, device_platform="tpu", jax_backend="tpu")
    other = _entry(10.0, bench="sweep", ts=3.0)
    bad = _entry(999.0, ts=4.0)
    bad["ok"] = False
    entries = [cpu, tpu, other, bad]
    same = ledger.matching(entries, bench="solver", env_of=cpu)
    assert [e["run_id"] for e in same] == [cpu["run_id"]]
    assert ledger.row_values([cpu, tpu], "solver/gs_8x8") == [100.0, 50.0]


def test_engine_opt_in_requires_flag_and_env(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_OBS_LEDGER", raising=False)
    assert ledger.engine_opt_in() is None
    obs.enable()
    assert ledger.engine_opt_in() is None
    path = str(tmp_path / "engine.jsonl")
    monkeypatch.setenv("REPRO_OBS_LEDGER", path)
    assert ledger.engine_opt_in() == path
    entry = ledger.record_engine_run("run_sweep", 0.5, count=10, derived="d")
    assert entry is not None
    (loaded,) = ledger.load(path)
    assert loaded["bench"] == "engine.run_sweep"
    assert loaded["rows"][0]["us_per_call"] == pytest.approx(0.5e6 / 10)
    obs.disable()
    assert ledger.record_engine_run("run_sweep", 0.5) is None


# ---------------------------------------------------------------------------
# Regression verdicts.
# ---------------------------------------------------------------------------


def _history(values, **over):
    return [
        _entry(v, ts=float(i)) for i, v in enumerate(values)
    ] if not over else [
        _entry(v, ts=float(i), **over) for i, v in enumerate(values)
    ]


def test_regress_flags_2x_slowdown():
    hist = _history([100.0, 102.0, 98.0, 101.0])
    current = _entry(200.0, ts=10.0)
    (v,) = regress.compare(current, hist)
    assert v.status == "regression"
    assert v.baseline_us == pytest.approx(100.5)
    assert v.ratio == pytest.approx(200.0 / 100.5)
    assert v.gating
    assert regress.has_regressions([v])


def test_regress_identical_timings_pass():
    hist = _history([100.0, 100.0, 100.0])
    (v,) = regress.compare(_entry(100.0, ts=10.0), hist)
    assert v.status == "ok"
    assert not v.gating


def test_regress_improvement_and_new_rows():
    hist = _history([100.0, 100.0, 100.0])
    (v,) = regress.compare(_entry(40.0, ts=10.0), hist)
    assert v.status == "improved"
    (v2,) = regress.compare(
        _entry(40.0, row="solver/other_row", ts=10.0), hist
    )
    assert v2.status == "new"


def test_regress_noisy_history_widens_threshold():
    quiet = regress.noise_threshold([100.0, 100.0, 100.0, 100.0])
    noisy = regress.noise_threshold([60.0, 140.0, 80.0, 120.0])
    assert quiet == regress.MIN_RATIO
    assert noisy > quiet
    # A ratio inside the noisy spread is not flagged.
    hist = _history([60.0, 140.0, 80.0, 120.0, 100.0])
    (v,) = regress.compare(_entry(150.0, ts=10.0), hist)
    assert v.status in ("ok", "insufficient")


def test_regress_ignores_other_environment_history():
    tpu_hist = _history(
        [10.0, 10.0, 10.0], device_platform="tpu", jax_backend="tpu"
    )
    # CPU run 10x slower than the TPU history: not a regression — there
    # is no CPU history at all.
    (v,) = regress.compare(_entry(100.0, ts=10.0), tpu_hist)
    assert v.status == "new"


def test_regress_single_history_point_is_insufficient():
    hist = _history([100.0])
    (v,) = regress.compare(_entry(500.0, ts=10.0), hist)
    assert v.status == "insufficient"
    assert not v.gating


def test_regress_skips_derived_only_rows():
    hist = _history([100.0, 100.0])
    (v,) = regress.compare(_entry(0.0, ts=10.0), hist)
    assert v.status == "skipped"


def test_regress_current_never_its_own_baseline():
    e = _entry(100.0, ts=1.0)
    (v,) = regress.compare(e, [e])
    assert v.status == "new"


# ---------------------------------------------------------------------------
# CLI gate (the acceptance criterion).
# ---------------------------------------------------------------------------


def _cli():
    return pytest.importorskip(
        "benchmarks.regress", reason="benchmarks/ needs repo-root cwd"
    )


def test_cli_flags_injected_2x_slowdown_and_passes_on_rerun(tmp_path):
    cli = _cli()
    path = str(tmp_path / "ledger.jsonl")
    for i, us in enumerate([100.0, 101.0, 99.0, 100.5]):
        ledger.append(_entry(us, ts=float(i)), path)
    # Injected 2x slowdown → exit 1.
    ledger.append(_entry(200.0, ts=10.0), path)
    assert cli.main(["--ledger", path]) == 1
    # --report-only never gates.
    assert cli.main(["--ledger", path, "--report-only"]) == 0

    # Rerun with identical timings → exit 0 (the slowdown entry is part
    # of history now but the median baseline shrugs off one outlier).
    path2 = str(tmp_path / "ledger2.jsonl")
    for i, us in enumerate([100.0, 101.0, 99.0, 100.5]):
        ledger.append(_entry(us, ts=float(i)), path2)
    ledger.append(_entry(100.2, ts=10.0), path2)
    assert cli.main(["--ledger", path2]) == 0


def test_cli_enforce_after_bootstraps_silently(tmp_path):
    cli = _cli()
    path = str(tmp_path / "ledger.jsonl")
    ledger.append(_entry(100.0, ts=0.0), path)
    ledger.append(_entry(100.0, ts=1.0), path)
    ledger.append(_entry(400.0, ts=2.0), path)  # regression, depth 2
    assert cli.main(["--ledger", path, "--enforce-after", "3"]) == 0
    assert cli.main(["--ledger", path, "--enforce-after", "2"]) == 1


def test_cli_empty_ledger_is_ok(tmp_path):
    cli = _cli()
    assert cli.main(["--ledger", str(tmp_path / "none.jsonl")]) == 0


# ---------------------------------------------------------------------------
# Report dashboard.
# ---------------------------------------------------------------------------


def _snapshot_with_histogram():
    obs.enable()
    h = obs.histogram("solver_sweeps", buckets=obs.SWEEPS_BUCKETS)
    for v in (8, 8, 16, 16, 16, 32, 64):
        h.observe(v)
    snap = obs.snapshot()
    obs.disable()
    return snap


def test_report_shows_metadata_and_quantiles(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    e = ledger.make_entry(
        "solver",
        [{"name": "solver/gs_8x8", "us_per_call": 100.0, "derived": ""}],
        meta=_meta(),
        metrics=_snapshot_with_histogram(),
    )
    ledger.append(e, path)
    out = report.render(ledger.load(path))
    assert "abc123def456"[:12] in out
    assert "cpu" in out
    assert "solver/gs_8x8" in out
    assert "solver_sweeps" in out
    assert "p95=" in out  # at least one histogram quantile rendered
    md = report.render(ledger.load(path), markdown=True)
    assert "| `solver/gs_8x8` |" in md


def test_report_quantiles_fall_back_to_buckets():
    # Old snapshot shape: no precomputed "quantiles" block.
    series = {
        "buckets": [
            {"le": "1", "count": 0},
            {"le": "2", "count": 2},
            {"le": "4", "count": 4},
            {"le": "+Inf", "count": 4},
        ],
        "count": 4,
    }
    qs = report.series_quantiles(series)
    assert qs["p50"] == pytest.approx(2.0)
    assert 2.0 <= qs["p99"] <= 4.0


def test_report_empty_ledger():
    assert "empty" in report.render([])


def test_report_cli_main(tmp_path, capsys):
    path = str(tmp_path / "ledger.jsonl")
    ledger.append(_entry(123.4, ts=1.0), path)
    assert report.main(["--ledger", path]) == 0
    out = capsys.readouterr().out
    assert "solver/gs_8x8" in out and "123.4us" in out


# ---------------------------------------------------------------------------
# Profiling hooks.
# ---------------------------------------------------------------------------


def test_prof_cost_flag_env_and_override(monkeypatch):
    prof.reset_cost()
    monkeypatch.delenv("REPRO_OBS_COST", raising=False)
    assert not prof.cost_enabled()
    monkeypatch.setenv("REPRO_OBS_COST", "1")
    assert prof.cost_enabled()
    prof.disable_cost()
    assert not prof.cost_enabled()
    prof.enable_cost()
    assert prof.cost_enabled()
    prof.reset_cost()


def test_prof_hlo_cost_and_instrumented_join():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    obs.enable()
    prof.enable_cost()
    try:
        fn = jax.jit(lambda a: (a @ a).sum())
        x = jnp.ones((32, 32))
        cost = prof.hlo_cost(fn, x)
        # CPU XLA implements cost analysis; tolerate absence elsewhere.
        if cost is not None:
            assert cost.get("flops", 0) > 0
        wrapped = prof.instrument_jit(fn, "matmul")
        wrapped(x)  # first call at this signature: records hlo_flops
        wrapped(x)  # same signature: no second cost record
        snap = obs.snapshot()
        # hlo_cost's compile above built the executable the calls run.
        names = [s.name for s in obs.spans() if s.name.startswith("matmul")]
        assert names == ["matmul[run]", "matmul[run]"]
        # Host time around an unblocked call is no device time: nothing
        # derives a throughput or a roofline share from it.
        for gone in ("jit_seconds", "achieved_flops_per_s",
                     "roofline_utilization"):
            assert gone not in snap
        if cost is not None and cost.get("flops"):
            assert snap["hlo_flops"]["series"][0]["value"] == cost["flops"]
            assert snap["hlo_bytes_accessed"]["series"][0]["labels"] == {
                "fn": "matmul"}
            assert prof.last_cost("matmul")["flops"] == cost["flops"]
    finally:
        prof.reset_cost()


def test_prof_instrument_jit_disabled_passthrough():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    fn = prof.instrument_jit(jax.jit(lambda a: a * 2), "double")
    assert float(fn(jnp.float32(2.0))) == 4.0
    assert obs.spans() == []


def test_prof_jax_profile_noop_without_dir(monkeypatch):
    monkeypatch.delenv("REPRO_OBS_JAX_PROFILE", raising=False)
    with prof.jax_profile() as d:
        assert d is None


def test_prof_jax_profile_captures(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import os

    obs.enable()
    logdir = str(tmp_path / "prof")
    with prof.jax_profile(logdir) as d:
        assert d == logdir
        jax.block_until_ready(jnp.ones(8) * 2)
    # The profiler wrote *something* under the logdir.
    found = [
        os.path.join(r, f) for r, _, fs in os.walk(logdir) for f in fs
    ]
    assert found, "jax.profiler.trace produced no files"
    assert any(s.name == "jax_profile" for s in obs.spans())


def test_prof_sample_memory_disabled_and_enabled():
    assert prof.sample_memory("map") is None  # disabled
    obs.enable()
    stats = prof.sample_memory("map")
    # CPU backends expose no memory_stats — None is the contract there;
    # when stats exist the gauges must have been registered.
    if stats is not None:
        snap = obs.snapshot()
        assert "device_bytes_in_use" in snap
    else:
        assert "device_bytes_in_use" not in obs.snapshot()


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_OBS_PEAK_FLOPS", "1.5e12")
    assert prof.peak_flops() == pytest.approx(1.5e12)
    monkeypatch.setenv("REPRO_OBS_PEAK_FLOPS", "garbage")
    assert prof.peak_flops("cpu") is None
    monkeypatch.delenv("REPRO_OBS_PEAK_FLOPS")
    assert prof.peak_flops("TPU v5 lite") == pytest.approx(197e12)
    assert prof.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == (
        pytest.approx(819e9)
    )
    with pytest.raises(KeyError, match="TPU v9"):
        prof.peak_flops("TPU v9")


# ---------------------------------------------------------------------------
# End-to-end: make_entry(metrics=snapshot) → report shows quantiles.
# ---------------------------------------------------------------------------


def test_quantile_round_trip_through_ledger_json(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    snap = _snapshot_with_histogram()
    e = ledger.make_entry("sweep", [("sweep/warm", 42.0, "")],
                          meta=_meta(), metrics=snap)
    ledger.append(e, path)
    (loaded,) = ledger.load(path)
    series = loaded["metrics"]["solver_sweeps"]["series"][0]
    qs = report.series_quantiles(series)
    assert qs["p50"] is not None and not math.isnan(qs["p50"])
    # p50 of (8,8,16,16,16,32,64) is 16; bucket-edge error bound: the
    # containing bucket also spans (8, 16].
    assert 8.0 <= qs["p50"] <= 16.0
