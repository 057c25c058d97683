"""The per-process cache of jitted group programs in `core.evaluate`."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import evaluate as ev
from repro.core.imac import IMACConfig
from repro.core.interconnect import Interconnect
from repro.core.solver import SolveOptions, tridiag_scan
from repro.distributed.sweep import MeshPlan

BASE = IMACConfig(array_rows=8, array_cols=8, tech="MRAM")


@pytest.fixture(autouse=True)
def _obs_on():
    obs.disable()
    obs.reset()
    obs.enable()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(scope="module")
def net():
    """A 16-8-4 MLP and eight inputs: two chunks of 8x8 parasitic tiles."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    params = [
        (0.3 * jax.random.normal(k1, (16, 8)), jnp.zeros(8)),
        (0.3 * jax.random.normal(k2, (8, 4)), jnp.zeros(4)),
    ]
    x = jax.random.uniform(k3, (8, 16))
    y = jnp.arange(8) % 4
    return params, x, y


def _run(net, cfgs, **kw):
    params, x, y = net
    return ev.evaluate_batch(params, x, y, cfgs, n_samples=8, chunk=4, **kw)


def _lookups():
    series = obs.snapshot().get("program_cache_lookups_total", {})
    return {s["labels"]["result"]: s["value"]
            for s in series.get("series", [])}


def _traced_names():
    return [s.name for s in obs.spans() if s.name == "jit_trace"]


def test_new_numeric_leaves_reuse_the_program_bitwise(net):
    other = dataclasses.replace(
        BASE, tech="PCM", interconnect=Interconnect(resistivity=3.0e-8),
        r_tia=7.0,
    )
    _run(net, [BASE, BASE])
    obs.reset()
    warm = _run(net, [other, BASE])
    assert _traced_names() == []
    chunks = [s.name for s in obs.spans() if s.name.startswith("solve_chunk")]
    assert chunks == ["solve_chunk[run]", "solve_chunk[run]"]
    assert _lookups() == {"hit": 1}

    ev.clear_program_cache()
    obs.reset()
    cold = _run(net, [other, BASE])
    assert _traced_names()
    assert _lookups() == {"miss": 1}
    assert [tuple(r) for r in warm] == [tuple(r) for r in cold]


KEY = jax.random.PRNGKey(7)

# (first call, second call) as (configuration, evaluate_batch keywords):
# each pair differs in one thing the group program reads.
MISSES = {
    "gs_tol": ((BASE, {}), (dataclasses.replace(BASE, gs_tol=1e-5), {})),
    "vdd": ((BASE, {}), (dataclasses.replace(BASE, vdd=0.9, vss=-0.9), {})),
    "parasitics": ((BASE, {}),
                   (dataclasses.replace(BASE, parasitics=False), {})),
    "dtype": ((BASE, {}), (dataclasses.replace(BASE, dtype=jnp.bfloat16), {})),
    "noise_per_config": ((BASE, {"noise_key": KEY}),
                         (BASE, {"noise_key": KEY, "noise_per_config": True})),
    "noise_key": ((BASE, {}), (BASE, {"noise_key": KEY})),
    "env_backend": ((BASE, {}), (BASE, {"env": "pallas"})),
    "mesh_plan": ((BASE, {}), (BASE, {"mesh_plan": MeshPlan(devices=1)})),
}


@pytest.mark.parametrize("case", sorted(MISSES))
def test_a_change_the_program_reads_misses(net, case, monkeypatch):
    monkeypatch.delenv("REPRO_SOLVER_BACKEND", raising=False)
    for cfg, kw in MISSES[case]:
        kw = dict(kw)
        if "env" in kw:
            monkeypatch.setenv("REPRO_SOLVER_BACKEND", kw.pop("env"))
        obs.reset()
        _run(net, [cfg, cfg], **kw)
        assert _lookups() == {"miss": 1}
        obs.reset()
        _run(net, [cfg, cfg], **kw)  # the same call again: a hit
        assert _lookups() == {"hit": 1}
        assert _traced_names() == []
    # Both programs stay cached side by side.
    assert len(ev._programs) == 2


def test_the_sharded_path_hits(net):
    plan = MeshPlan(devices=1)
    first = _run(net, [BASE, BASE], mesh_plan=plan)
    assert [s.name for s in obs.spans() if s.name == "shard_stage"]
    obs.reset()
    again = _run(net, [BASE, BASE], mesh_plan=MeshPlan(devices=1))
    assert _lookups() == {"hit": 1}
    assert _traced_names() == []
    assert [tuple(r) for r in first] == [tuple(r) for r in again]


class _Unhashable:
    """A custom inner solve that cannot be a cache key."""

    __hash__ = None

    def __eq__(self, other):
        return isinstance(other, _Unhashable)

    def __call__(self, dl, d, du, b):
        return tridiag_scan(dl, d, du, b)


def test_counter_counts_hits_misses_and_bypasses(net):
    options = SolveOptions(backend=_Unhashable())
    _run(net, [BASE, BASE])
    _run(net, [BASE, BASE])
    bypassed = _run(net, [BASE, BASE], solve_options=options)
    _run(net, [BASE, BASE], solve_options=options)
    _run(net, [BASE, BASE])
    assert _lookups() == {"miss": 1, "hit": 2, "bypass": 2}
    assert len(ev._programs) == 1
    assert bypassed[0].accuracy == _run(net, [BASE, BASE])[0].accuracy


def test_the_least_recently_used_program_is_evicted(net, monkeypatch):
    monkeypatch.setattr(ev, "PROGRAM_CACHE_SIZE", 2)
    a, b, c = (dataclasses.replace(BASE, gs_tol=t) for t in (1e-6, 1e-5, 1e-4))
    for cfg in (a, b, a, c, a, b):
        _run(net, [cfg, cfg])
    # a miss, b miss, a hit, c miss (evicts b), a hit, b miss (evicts c).
    assert _lookups() == {"miss": 4, "hit": 2}
    assert len(ev._programs) == 2


def test_concurrent_lookups_keep_the_bound_and_the_programs(monkeypatch):
    import sys
    import threading

    monkeypatch.setattr(ev, "PROGRAM_CACHE_SIZE", 4)
    monkeypatch.setattr(ev, "_build_run_chunk",
                        lambda prog, noisy, shard: ("built", prog))
    errors, wrong = [], []

    def worker(seed):
        try:
            for i in range(400):
                prog = (seed * 7 + i) % 9
                run_chunk, _ = ev._run_chunk_for(prog, False, None)
                if run_chunk != ("built", prog):
                    wrong.append((prog, run_chunk))
        except Exception as e:  # reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(s,)) for s in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == [] and wrong == []
    assert len(ev._programs) <= 4
