"""Readers of the program's trace counter and device wait.

The program counts JAX's traces to a jaxpr in `jit_traces_total` and
names the host's wait for the chip after each solve `device_wait`. A
program without them gives None.
"""
from __future__ import annotations

from benchlib.readers import points_done


def jit_traces_per_point(ctx):
    """JAX traces to a jaxpr (outer and nested), per design point: 0.0 in a
    window that traced nothing, None where the program has no counter."""
    if "jit_traces_total" not in ctx.registered or not points_done(ctx):
        return None
    series = ctx.snapshot.get("jit_traces_total", {}).get("series", [])
    return sum(s["value"] for s in series) / points_done(ctx)


def device_wait_ms_per_point(ctx):
    """Host time blocked on the chip after the solves, per design point."""
    waits = [sp.duration for sp in ctx.spans if sp.name == "device_wait"]
    if not waits or not points_done(ctx):
        return None
    return 1e3 * sum(waits) / points_done(ctx)
