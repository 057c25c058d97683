"""Arithmetic shared by the metric readers in `bench/metrics/`.

Each reader takes the run's context and returns a number, or None when
its run has nothing to read (no trace, no span, no solve).
"""
from __future__ import annotations

import sys

import numpy as np

from benchlib import peaks, work


def points_done(ctx) -> int:
    return sum(len(c.points) for c in ctx.calls)


def latencies_ms(ctx) -> np.ndarray:
    return np.asarray([c.latency_s for c in ctx.calls]) * 1e3


def self_ms_per_point(ctx, names):
    """Self time of the program's spans named `names`, per point."""
    if not ctx.spans or not points_done(ctx):
        return None
    child = {}
    for sp in ctx.spans:
        if sp.parent is not None:
            child[sp.parent] = child.get(sp.parent, 0.0) + sp.duration
    mine = [sp for sp in ctx.spans if sp.name in names]
    if not mine:
        return None
    self_s = sum(sp.duration - child.get(sp.sid, 0.0) for sp in mine)
    return 1e3 * self_s / points_done(ctx)


def host_ms_per_point(ctx):
    """Self time of the engine's and evaluation's host spans, per point."""
    return self_ms_per_point(ctx, ctx.host_spans)


def sweeps_per_solve(ctx):
    """Mean Gauss-Seidel sweeps per layer solve of each group."""
    series = ctx.snapshot.get("solver_sweeps", {}).get("series", [])
    count = sum(s["count"] for s in series)
    return sum(s["sum"] for s in series) / count if count else None


def solve_roofline(ctx):
    """Share of the roofline of the circuit solves' counted work, over the
    device busy time of the traced window."""
    if not ctx.trace or ctx.trace["busy_s"] <= 0 or not ctx.traffic.parasitics:
        return None
    ops = nbytes = 0
    topology = ctx.config["topology"]
    for call in ctx.calls:
        groups = {}
        for p in call.points:
            groups.setdefault(p.group, []).append(p)
        for members in groups.values():
            w = work.solve_work(topology, members[0].partitioning,
                                len(members), ctx.traffic.n_samples)
            ops += w["ops"]
            nbytes += w["bytes"]
    share = work.roofline_share(ops, nbytes, ctx.trace["busy_s"],
                                peaks.peaks_for(ctx.device_kind), ctx.chips)
    if share is None:
        return None
    print(f"bench: solve_roofline bound={share[1]} ops={ops} bytes={nbytes} "
          f"busy_s={ctx.trace['busy_s']}", file=sys.stderr, flush=True)
    return share[0]


def idle_pct(ctx):
    if not ctx.trace or ctx.trace["busy_s"] <= 0:
        return None
    return ctx.trace["idle_pct"]
