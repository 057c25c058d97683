"""Tests for repro.obs: tracer, metrics registry, exporters, overhead."""
import importlib
import json
import threading
import tracemalloc

import pytest

from repro import obs
from repro.obs import metrics as obs_metrics

# The package re-exports the trace() function under the submodule's
# name, so reach the module itself through importlib.
obs_trace = importlib.import_module("repro.obs.trace")


@pytest.fixture(autouse=True)
def _clean_obs():
    """Each test starts disabled with empty stores and leaves the same."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# ---------------------------------------------------------------------------
# Span tracer.
# ---------------------------------------------------------------------------


def test_nested_spans_record_parent_and_depth():
    obs.enable()
    with obs.trace("outer", {"k": 1}):
        with obs.trace("inner"):
            with obs.trace("leaf"):
                pass
        with obs.trace("sibling"):
            pass
    by_name = {s.name: s for s in obs.spans()}
    assert set(by_name) == {"outer", "inner", "leaf", "sibling"}
    assert by_name["outer"].parent is None
    assert by_name["inner"].parent == by_name["outer"].sid
    assert by_name["leaf"].parent == by_name["inner"].sid
    assert by_name["sibling"].parent == by_name["outer"].sid
    assert by_name["leaf"].depth == 2
    assert by_name["outer"].duration >= by_name["inner"].duration
    tree = obs.span_tree()
    assert tree.splitlines()[0].startswith("outer")
    assert "    leaf" in tree


def test_traced_decorator_checks_flag_per_call():
    calls = []

    @obs.traced("decorated")
    def fn(v):
        calls.append(v)
        return v * 2

    assert fn(3) == 6          # disabled: no span
    assert obs.spans() == []
    obs.enable()
    assert fn(4) == 8          # enabled later: spans appear
    assert [s.name for s in obs.spans()] == ["decorated"]
    assert calls == [3, 4]


def test_span_set_attributes_appear_in_exports():
    obs.enable()
    with obs.trace("work") as sp:
        sp.set("items", 7)
    (span,) = obs.spans()
    assert span.attrs == {"items": 7}
    (ev,) = obs.chrome_trace()["traceEvents"]
    assert ev["args"] == {"items": 7}


def test_chrome_trace_round_trip(tmp_path):
    obs.enable()
    with obs.trace("root", {"grid": "2x2"}):
        with obs.trace("child"):
            pass
    obs.add_instant("mark", {"cause": "unit-test"})
    path = tmp_path / "trace.json"
    obs.export_chrome_trace(str(path))
    loaded = json.loads(path.read_text())
    events = loaded["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert {e["name"] for e in complete} == {"root", "child"}
    assert [e["name"] for e in instants] == ["mark"]
    for e in complete:
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    root = next(e for e in complete if e["name"] == "root")
    child = next(e for e in complete if e["name"] == "child")
    assert root["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= root["ts"] + root["dur"] + 1e-3


def test_thread_safety_smoke():
    obs.enable()

    def worker(i):
        for j in range(50):
            with obs.trace(f"t{i}", {"j": j}):
                obs.counter("thread_ops_total").inc()

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recorded = obs.spans()
    assert len(recorded) == 8 * 50
    # Nesting state is thread-local: every worker span is a root span.
    assert all(s.parent is None for s in recorded)
    assert obs.counter("thread_ops_total").value == 8 * 50
    assert len({s.sid for s in recorded}) == len(recorded)


def test_span_records_error_attribute_when_body_raises():
    obs.enable()
    with pytest.raises(RuntimeError, match="boom"):
        with obs.trace("outer"):
            with obs.trace("failing"):
                raise RuntimeError("boom")
    by_name = {s.name: s for s in obs.spans()}
    assert by_name["failing"].attrs["error"] == "RuntimeError: boom"
    # The exception unwound through the parent too — both are closed,
    # both carry the error, and nesting state is intact for new spans.
    assert by_name["outer"].attrs["error"] == "RuntimeError: boom"
    with obs.trace("after"):
        pass
    assert {s.name: s.parent for s in obs.spans()}["after"] is None


def test_span_error_survives_existing_attrs():
    obs.enable()
    with pytest.raises(ValueError):
        with obs.trace("work", {"items": 3}):
            raise ValueError("bad input")
    (span,) = obs.spans()
    assert span.attrs["items"] == 3
    assert span.attrs["error"].startswith("ValueError")


def test_chrome_exporter_flushes_open_spans():
    obs.enable()
    outer = obs.trace("outer")
    outer.__enter__()
    with obs.trace("closed"):
        pass
    events = obs.chrome_trace()["traceEvents"]
    by_name = {e["name"]: e for e in events if e["ph"] == "X"}
    assert set(by_name) == {"outer", "closed"}
    assert by_name["outer"]["args"]["unfinished"] is True
    assert "unfinished" not in by_name["closed"]["args"]
    assert by_name["outer"]["dur"] >= by_name["closed"]["dur"]
    # Closing the span moves it to the finished list: no double export.
    outer.__exit__(None, None, None)
    events = obs.chrome_trace()["traceEvents"]
    outers = [e for e in events if e["name"] == "outer"]
    assert len(outers) == 1
    assert "unfinished" not in outers[0]["args"]
    assert obs_trace.live_spans() == []


def test_export_chrome_trace_with_open_span_round_trips(tmp_path):
    obs.enable()
    sp = obs.trace("crashing_stage")
    sp.__enter__()
    path = tmp_path / "trace.json"
    obs.export_chrome_trace(str(path))
    loaded = json.loads(path.read_text())
    (ev,) = [
        e for e in loaded["traceEvents"] if e["name"] == "crashing_stage"
    ]
    assert ev["args"]["unfinished"] is True
    assert ev["dur"] >= 0.0
    sp.__exit__(None, None, None)


def test_instrument_jit_splits_compile_and_run():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    obs.enable()
    fn = obs.instrument_jit(jax.jit(lambda v: v * 2.0), "double")
    x = jnp.arange(4.0)
    fn(x)
    fn(x)
    fn(jnp.arange(8.0))  # new shape: fresh lowering + compile
    names = [s.name for s in obs.spans() if s.name.startswith("double")]
    assert names == ["double[compile]", "double[run]", "double[compile]"]


# ---------------------------------------------------------------------------
# Metrics registry.
# ---------------------------------------------------------------------------


def test_counter_and_gauge_basics():
    obs.enable()
    c = obs.counter("reqs_total", {"engine": "explore"})
    c.inc()
    c.inc(2)
    assert c.value == 3
    assert obs.counter("reqs_total", {"engine": "explore"}) is c
    with pytest.raises(ValueError):
        c.inc(-1)
    g = obs.gauge("depth")
    g.set(5)
    g.inc()
    assert g.value == 6


def test_histogram_bucket_edges():
    obs.enable()
    h = obs.histogram("lat", buckets=obs.exponential_buckets(1.0, 2.0, 3))
    assert h.edges == (1.0, 2.0, 4.0)
    for v in (0.5, 1.0, 1.5, 4.0, 100.0):
        h.observe(v)
    # bisect_left: observations equal to an edge land in that bucket.
    assert h.counts == [2, 1, 1, 1]
    cum = h.cumulative()
    assert cum == [(1.0, 2), (2.0, 3), (4.0, 4), (float("inf"), 5)]
    assert h.count == 5
    assert h.sum == pytest.approx(107.0)


def test_histogram_quantiles_within_bucket_edge_error():
    np = pytest.importorskip("numpy")

    obs.enable()
    h = obs.histogram(
        "lat_q", buckets=obs.exponential_buckets(1.0, 2.0, 12)
    )
    rng = np.random.default_rng(0)
    vals = rng.uniform(0.5, 900.0, size=2000)
    for v in vals:
        h.observe(float(v))
    edges = (0.0,) + h.edges
    for q in (0.5, 0.95, 0.99):
        est = h.quantile(q)
        exact = float(np.quantile(vals, q))
        # The estimate must land in the same bucket as the exact value,
        # i.e. within that bucket's width of it.
        idx = int(np.searchsorted(edges, exact, side="left"))
        width = edges[min(idx, len(edges) - 1)] - edges[max(idx - 1, 0)]
        assert abs(est - exact) <= width, (q, est, exact, width)


def test_histogram_quantile_exact_edges_and_interpolation():
    obs.enable()
    h = obs.histogram("q_edges", buckets=(1.0, 2.0, 4.0))
    for v in (2.0, 2.0, 4.0, 4.0):
        h.observe(v)
    # All mass sits on the (1,2] and (2,4] buckets: p50 = the 2.0 edge.
    assert h.quantile(0.5) == pytest.approx(2.0)
    # p100 = upper edge of the last occupied bucket.
    assert h.quantile(1.0) == pytest.approx(4.0)
    # Halfway into the second bucket's mass: linear interpolation.
    assert 2.0 < h.quantile(0.75) <= 4.0


def test_histogram_quantile_overflow_clamps_to_top_edge():
    obs.enable()
    h = obs.histogram("q_over", buckets=(1.0, 2.0))
    h.observe(1000.0)
    h.observe(2000.0)
    assert h.quantile(0.5) == pytest.approx(2.0)
    assert h.quantiles()["p99"] == pytest.approx(2.0)


def test_histogram_quantile_empty_and_validation():
    obs.enable()
    h = obs.histogram("q_empty", buckets=(1.0,))
    assert h.quantile(0.5) is None
    assert h.quantiles() == {"p50": None, "p95": None, "p99": None}
    h.observe(0.5)
    with pytest.raises(ValueError):
        h.quantile(1.5)
    assert obs.quantile_from_cumulative([], 0.5) is None


def test_snapshot_surfaces_quantiles():
    obs.enable()
    h = obs.histogram("snap_q", buckets=(1.0, 2.0, 4.0))
    for v in (1.5, 1.5, 3.0, 3.0):
        h.observe(v)
    snap = json.loads(obs.export_json())
    series = snap["snap_q"]["series"][0]
    assert set(series["quantiles"]) == {"p50", "p95", "p99"}
    assert 1.0 <= series["quantiles"]["p50"] <= 2.0
    assert 2.0 < series["quantiles"]["p99"] <= 4.0


def test_exponential_buckets_validation():
    assert obs.exponential_buckets(1e-2, 10.0, 3) == (1e-2, 1e-1, 1.0)
    with pytest.raises(ValueError):
        obs.exponential_buckets(0.0, 2.0, 4)
    with pytest.raises(ValueError):
        obs.exponential_buckets(1.0, 1.0, 4)


def test_metric_kind_conflict_raises():
    obs.enable()
    obs.counter("x_total")
    with pytest.raises(ValueError):
        obs.gauge("x_total")


def test_event_increments_labeled_counter_and_log():
    obs.enable()
    obs.event("backend_fallback", cause="vmem_budget", tile="512x512")
    obs.event("backend_fallback", cause="vmem_budget", tile="512x512")
    obs.event("backend_fallback", cause="interpret_mode", extra=[1, 2])
    recs = obs.events("backend_fallback")
    assert len(recs) == 3
    assert recs[-1]["fields"]["extra"] == [1, 2]
    exp = obs.export_prometheus()
    assert (
        'backend_fallback_total{cause="vmem_budget",tile="512x512"} 2' in exp
    )
    assert 'cause="interpret_mode"' in exp
    # Events also mark the span timeline.
    instants = [
        e for e in obs.chrome_trace()["traceEvents"] if e["ph"] == "i"
    ]
    assert len(instants) == 3


def test_prometheus_export_format():
    obs.enable()
    obs.counter("hits_total").inc(4)
    h = obs.histogram("sw", buckets=(1.0, 2.0))
    h.observe(1.5)
    h.observe(10.0)
    text = obs.export_prometheus()
    lines = text.splitlines()
    assert "# TYPE hits_total counter" in lines
    assert "hits_total 4" in lines
    assert "# TYPE sw histogram" in lines
    assert 'sw_bucket{le="1"} 0' in lines
    assert 'sw_bucket{le="2"} 1' in lines
    assert 'sw_bucket{le="+Inf"} 2' in lines
    assert "sw_sum 11.5" in lines
    assert "sw_count 2" in lines


def test_snapshot_json_round_trip():
    obs.enable()
    obs.counter("c_total", {"k": "v"}).inc()
    obs.histogram("h", buckets=(1.0,)).observe(0.5)
    snap = json.loads(obs.export_json())
    assert snap["c_total"]["type"] == "counter"
    assert snap["c_total"]["series"][0]["labels"] == {"k": "v"}
    hseries = snap["h"]["series"][0]
    assert hseries["count"] == 1
    assert hseries["buckets"][0]["count"] == 1


# ---------------------------------------------------------------------------
# Disabled mode.
# ---------------------------------------------------------------------------


def test_disabled_mode_returns_shared_noops_and_records_nothing():
    assert obs.trace("x") is obs_trace._NOOP
    assert obs.trace("y", {"a": 1}) is obs_trace._NOOP
    assert obs.counter("c_total") is obs_metrics._NOOP
    assert obs.histogram("h") is obs_metrics._NOOP
    with obs.trace("x") as sp:
        sp.set("k", "v")
    obs.counter("c_total").inc()
    obs.event("nothing", cause="disabled")
    obs.add_instant("nothing")
    obs.enable()
    assert obs.spans() == []
    assert obs.events() == []
    assert obs.export_prometheus() == ""


def test_disabled_mode_zero_allocations_on_hot_path():
    c = obs.counter("hot_total")
    span_fn, counter_fn = obs.trace, obs.counter
    # Warm up any lazy interning, then measure.
    for _ in range(10):
        with span_fn("hot"):
            c.inc()
    tracemalloc.start()
    for _ in range(1000):
        with span_fn("hot"):
            counter_fn("hot_total").inc()
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # The loop itself allocates nothing measurable: no span objects, no
    # metric instances, no dicts (slack covers tracemalloc's own frame
    # bookkeeping).
    assert peak < 4096, f"disabled-mode hot path allocated {peak} bytes"


def test_env_var_enables(monkeypatch):
    from repro.obs import state

    monkeypatch.setenv("REPRO_OBS", "1")
    # Fresh evaluation of the env logic (module already imported).
    assert state._env_enabled()
    monkeypatch.setenv("REPRO_OBS", "0")
    assert not state._env_enabled()
    monkeypatch.delenv("REPRO_OBS")
    assert not state._env_enabled()


# ---------------------------------------------------------------------------
# Build spans from JAX's compile events, device_wait, profiler annotations.
# ---------------------------------------------------------------------------

BUILD_SPANS = obs_trace.BUILD_SPANS
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


@pytest.fixture(scope="module")
def tiny_batch():
    """A 16-8-4 MLP on 8x8 parasitic tiles: two configs, two chunks."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.imac import IMACConfig

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    params = [
        (0.3 * jax.random.normal(k1, (16, 8)), jnp.zeros(8)),
        (0.3 * jax.random.normal(k2, (8, 4)), jnp.zeros(4)),
    ]
    x = jax.random.uniform(k3, (8, 16))
    y = jnp.arange(8) % 4
    cfgs = [IMACConfig(array_rows=8, array_cols=8, tech=t)
            for t in ("MRAM", "PCM")]
    return params, x, y, cfgs


def _evaluate(batch):
    from repro.core.evaluate import evaluate_batch

    params, x, y, cfgs = batch
    return evaluate_batch(params, x, y, cfgs, n_samples=8, chunk=4)


def _self_times(spans):
    child = {}
    for s in spans:
        if s.parent is not None:
            child[s.parent] = child.get(s.parent, 0.0) + s.duration
    return {s.sid: s.duration - child.get(s.sid, 0.0) for s in spans}


def _repro_listeners():
    from jax._src import monitoring

    return [cb for cb in (monitoring.get_event_listeners()
                          + monitoring.get_event_time_span_listeners())
            if getattr(cb, "__module__", "").startswith("repro")]


def test_import_registers_no_jax_listener():
    import os
    import subprocess
    import sys

    code = (
        "import repro, repro.obs, repro.core.evaluate\n"
        "from jax._src import monitoring as m\n"
        "cbs = m.get_event_listeners() + m.get_event_time_span_listeners()\n"
        "print(sum(getattr(c, '__module__', '').startswith('repro') "
        "for c in cbs))\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "REPRO_OBS"}
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip().splitlines()[-1] == "0"


def test_disabled_obs_has_no_listener_and_adds_no_block(tiny_batch,
                                                        monkeypatch):
    import jax

    obs.enable()
    assert len(_repro_listeners()) == 2
    obs.disable()
    assert _repro_listeners() == []

    blocks = []
    real = jax.block_until_ready

    def counting(x):
        blocks.append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    _evaluate(tiny_batch)
    assert blocks == []
    obs.enable()
    _evaluate(tiny_batch)
    assert len(blocks) == 1  # the device_wait span's, and no other
    assert [s.name for s in obs.spans()].count("device_wait") == 1


def test_obs_adds_no_jax_trace_to_evaluate_batch(tiny_batch):
    from jax import monitoring

    from repro.core.evaluate import clear_program_cache

    traces = []

    def on_span(event, start, end, **kw):
        if event == TRACE_EVENT:
            traces.append(kw.get("fun_name"))

    monitoring.register_event_time_span_listener(on_span)
    try:
        first, second = {}, {}
        # One round each way first: module-level jitted helpers trace once
        # per process, and the telemetry's helpers only with obs on.
        for on in (False, True, False, True):
            (obs.enable if on else obs.disable)()
            clear_program_cache()
            traces.clear()
            _evaluate(tiny_batch)
            first[on] = len(traces)
            traces.clear()
            _evaluate(tiny_batch)
            second[on] = len(traces)
    finally:
        monitoring.unregister_event_time_span_listener(on_span)
    # A new group program traces the same with obs on and off; the same
    # call again reuses it and traces nothing.
    assert first[True] == first[False] > 0
    assert second[True] == second[False] == 0
    snap = obs.snapshot()
    assert snap["jit_traces_total"]["series"][0]["value"] >= first[True]


def test_reused_jit_runs_without_build_spans():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    def make():
        def scale(v):
            return jnp.sin(v) * 2.0

        return scale

    obs.enable()
    x = jnp.arange(4.0)
    reused = obs.instrument_jit(jax.jit(make()), "chunk")
    reused(x)
    obs.reset()
    reused(x)
    assert [s.name for s in obs.spans()] == ["chunk[run]"]
    obs.reset()
    obs.instrument_jit(jax.jit(make()), "chunk")(x)
    spans = obs.spans()
    assert any(s.name == "jit_trace" for s in spans)
    assert spans[-1].name == "chunk[compile]"
    top = spans[-1]
    for s in spans[:-1]:
        assert top.t_start <= s.t_start <= s.t_end <= top.t_end
    roots = [s for s in spans if s.parent == top.sid]
    assert [s.name for s in roots][:2] == ["jit_trace", "jit_lower"]
    assert roots[0].attrs["fun_name"] == "scale"
    names = [s.name for s in spans]
    assert "jit_lower" in names
    assert "executable_fetch" in names or "backend_compile" in names
    snap = obs.snapshot()
    assert (snap["jit_traces_total"]["series"][0]["value"]
            == names.count("jit_trace"))


def test_build_spans_nest_without_negative_self_time(tiny_batch):
    obs.enable()
    _evaluate(tiny_batch)
    spans = obs.spans()
    by_id = {s.sid: s for s in spans}
    self_s = _self_times(spans)
    chunks = [s for s in spans if s.name.startswith("solve_chunk")]
    assert [s.name for s in chunks] == ["solve_chunk[compile]",
                                        "solve_chunk[run]"]
    build = [s for s in spans if s.name in BUILD_SPANS]
    nested = [s for s in build if by_id[s.parent].name in BUILD_SPANS]
    assert nested, "inner jits trace inside the outer trace"
    for s in build:
        # Float rounding of nested differences only.
        assert self_s[s.sid] >= -1e-12, (s.name, self_s[s.sid])
        parent = by_id[s.parent]
        assert parent.t_start <= s.t_start <= s.t_end <= parent.t_end
        assert s.depth == parent.depth + 1
    under = [s for s in build
             if by_id[s.parent].name == "solve_chunk[compile]"]
    assert {s.name for s in under} >= {"jit_trace", "jit_lower"}
    total = sum(self_s[s.sid] for s in build
                if _ancestor(s, chunks[0].sid, by_id))
    assert 0 < total <= chunks[0].duration
    names = [s.name for s in spans if s.parent == by_id[chunks[0].parent].parent]
    assert names.index("solve") < names.index("device_wait") < names.index(
        "measure")


def _ancestor(span, sid, by_id):
    while span.parent is not None:
        if span.parent == sid:
            return True
        span = by_id[span.parent]
    return False


def test_spans_are_profiler_annotations(tmp_path):
    jax = pytest.importorskip("jax")
    import glob
    import os

    import jax.numpy as jnp
    from jax.profiler import ProfileData

    obs.enable()
    fn = obs.instrument_jit(jax.jit(lambda v: v + 1.0), "chunk_prof")
    with jax.profiler.trace(str(tmp_path)):
        with obs.trace("outer_prof"):
            fn(jnp.arange(4.0))

        @obs.traced("decorated_prof")
        def work():
            return fn(jnp.arange(4.0))

        jax.block_until_ready(work())
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    host = [e.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for line in p.lines for e in line.events]
    for name in ("outer_prof", "decorated_prof", "chunk_prof"):
        assert name in host, name
    assert not {"pass", "request"} & {s.name for s in obs.spans()}
