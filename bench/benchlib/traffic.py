"""The digit-MLP traffic generator: reads a mix's parameters and drives the program.

A mix's `kind` names its module in `bench/kinds/` (`<kind>.py`), which
makes the run's inputs and weights, builds the object that drives the
calls, and owns the comparison and the count of failed results (see
`bench/run.py`). The `sweep` and `loop` kinds drive this generator; the
`variability` kind reuses its warm-up, window and inputs.

Two kinds of mix here, both through `repro.explore.run_sweep` with no
result cache, on the program's default solver path:

  sweep  every design point of the configuration in one `run_sweep` call
         (a pass) over the next `n_samples` test inputs of the seeded
         pool; passes back to back. `mesh_devices` shards each structure
         group's stacked configurations over that many chips.
  loop   closed loop, one client: one design point per `run_sweep` call
         (a request), drawn in rounds that each hold every point once in
         an order shuffled from the seed, over the next `n_samples`
         inputs of the pool.

Every pass or request is wrapped in a `jax.profiler.TraceAnnotation`
(`pass`, `request`; `warmup` during set-up), which names the idle gaps of
a traced run.
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import numpy as np

from benchlib import data
from benchlib.points import Point, design_points, program_config


@dataclasses.dataclass
class Call:
    """One `run_sweep` call of the window."""

    points: "list[Point]"
    offset: int            # first input of the pool slice
    t_sent: float          # perf_counter when the call was made
    t_done: float          # perf_counter when the results were on the host
    results: list          # IMACResult per point, in point order

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_sent


class Traffic:
    def __init__(self, cfg: dict, mix: dict, seed: int, params, x_pool, y_pool,
                 dtype=None):
        self.cfg, self.mix = cfg, mix
        self.params, self.x_pool, self.y_pool = params, x_pool, y_pool
        self.kind = mix["kind"]
        self.label = {"sweep": "pass", "loop": "request"}[self.kind]
        self.n_samples = int(mix["n_samples"])
        self.chunk = int(mix.get("chunk", self.n_samples))
        self.parasitics = bool(mix["parasitics"])
        self.points = design_points(cfg)
        self.program = {
            p.name: program_config(cfg, p, parasitics=self.parasitics,
                                   dtype=dtype)
            for p in self.points
        }
        self.slices = x_pool.shape[0] // self.n_samples
        self.shard = None
        if mix.get("mesh_devices"):
            from repro.distributed.sweep import MeshPlan

            self.shard = MeshPlan(devices=int(mix["mesh_devices"]))
        self._stream = np.random.default_rng(data.derive(seed, 3))
        self._round: "list[Point]" = []
        self._next_slice = 0

    # -- what one call evaluates -------------------------------------------
    def next_points(self) -> "list[Point]":
        if self.kind == "sweep":
            return self.points
        if not self._round:
            order = self._stream.permutation(len(self.points))
            self._round = [self.points[i] for i in order]
        return [self._round.pop()]

    def warmup_points(self) -> "list[list[Point]]":
        """One call per compiled structure the window will use."""
        if self.kind == "sweep":
            return [self.points]
        first = {}
        for p in self.points:
            first.setdefault(p.group, p)
        return [[p] for p in first.values()]

    # -- calls ---------------------------------------------------------------
    def call(self, points: "list[Point]", label: str) -> Call:
        from repro.explore import run_sweep

        offset = (self._next_slice % self.slices) * self.n_samples
        self._next_slice += 1
        xs = self.x_pool[offset:offset + self.n_samples]
        ys = self.y_pool[offset:offset + self.n_samples]
        named = [(p.name, self.program[p.name]) for p in points]
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(label):
            out = run_sweep(self.params, xs, ys, named,
                            n_samples=self.n_samples, chunk=self.chunk,
                            cache=None, shard=self.shard)
        t1 = time.perf_counter()
        if [r.name for r in out] != [p.name for p in points]:
            raise RuntimeError("run_sweep returned points out of order")
        return Call(points, offset, t0, t1, [r.result for r in out])

    def warm_up(self) -> None:
        for points in self.warmup_points():
            self.call(points, "warmup")

    def window(self, seconds: float, label: str,
               on_call=None) -> "tuple[list[Call], float]":
        """Calls back to back until `seconds` have passed; whole calls only.

        Returns the calls and the window's length: from the first call's
        start to the last result on the host.
        """
        calls = []
        t0 = time.perf_counter()
        while True:
            c = self.call(self.next_points(), label)
            calls.append(c)
            if on_call is not None:
                on_call(c)
            if c.t_done - t0 >= seconds:
                break
        return calls, calls[-1].t_done - calls[0].t_sent

    def inputs(self, call: Call):
        xs = np.asarray(self.x_pool[call.offset:call.offset + self.n_samples])
        ys = np.asarray(self.y_pool[call.offset:call.offset + self.n_samples])
        return xs, ys


def on_digits(cfg: dict, mix: dict, seed: int, cls=Traffic, **kw):
    """(traffic, notes): a `cls` over the seed's digit pool and trained MLP
    (`benchlib.data`); `kw` goes to its constructor."""
    params, x_pool, y_pool, digital_acc = data.make_workload(seed, cfg)
    return (cls(cfg, mix, seed, params, x_pool, y_pool, **kw),
            {"digital_accuracy": digital_acc})


def failed(calls) -> int:
    """Results (design points, or Monte-Carlo trials) whose accuracy or
    power is not finite."""
    return sum(1 for c in calls for r in c.results
               if not math.isfinite(r.avg_power + r.accuracy))
