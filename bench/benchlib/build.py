"""Readers of the program's build spans, build counters and device wait.

The program records `jit_trace`, `jit_lower`, `executable_fetch` and
`backend_compile` spans from JAX's compile events, counts traces in
`jit_traces_total`, and names the host's wait for the chip after each
solve `device_wait`. A program without them gives None.
"""
from __future__ import annotations

from benchlib.readers import points_done

BUILD_SPANS = ("jit_trace", "jit_lower", "executable_fetch", "backend_compile")


def build_ms_per_point(ctx):
    """Self time of the build spans, per design point."""
    build = [sp for sp in ctx.spans if sp.name in BUILD_SPANS]
    if not build or not points_done(ctx):
        return None
    child = {}
    for sp in ctx.spans:
        if sp.parent is not None:
            child[sp.parent] = child.get(sp.parent, 0.0) + sp.duration
    self_s = sum(sp.duration - child.get(sp.sid, 0.0) for sp in build)
    return 1e3 * self_s / points_done(ctx)


def jit_traces_per_point(ctx):
    """JAX traces to a jaxpr (outer and nested), per design point."""
    series = ctx.snapshot.get("jit_traces_total", {}).get("series", [])
    if not series or not points_done(ctx):
        return None
    return sum(s["value"] for s in series) / points_done(ctx)


def device_wait_ms_per_point(ctx):
    """Host time blocked on the chip after the solves, per design point."""
    waits = [sp.duration for sp in ctx.spans if sp.name == "device_wait"]
    if not waits or not points_done(ctx):
        return None
    return 1e3 * sum(waits) / points_done(ctx)
