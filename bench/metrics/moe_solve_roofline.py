"""MoE expert solves' share of the chip's roofline: the counted work of the
window's (token, expert) pairs (`benchlib.moe_work`) over device busy time."""
import sys

from benchlib import moe_work, peaks, work


def read(ctx):
    if not ctx.trace or ctx.trace["busy_s"] <= 0 or "imac" not in ctx.config:
        return None
    ops = nbytes = 0
    for call in ctx.calls:
        experts = {p.name.split("/", 1)[1] for p in call.points}
        w = moe_work.call_work(ctx.config, len(call.points), len(experts))
        ops += w["ops"]
        nbytes += w["bytes"]
    share = work.roofline_share(ops, nbytes, ctx.trace["busy_s"],
                                peaks.peaks_for(ctx.device_kind), ctx.chips)
    if share is None:
        return None
    print(f"bench: moe_solve_roofline bound={share[1]} ops={ops} bytes={nbytes} "
          f"busy_s={ctx.trace['busy_s']}", file=sys.stderr, flush=True)
    return share[0]
