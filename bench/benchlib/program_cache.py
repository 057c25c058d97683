"""Reader of the program's group-program cache counter.

The program counts each look-up of a structure group's jitted solve in
`program_cache_lookups_total`, labelled `result` = `hit`, `miss` or
`bypass`. A program without the counter gives None.
"""
from __future__ import annotations


def hit_pct(ctx):
    """Share of the window's look-ups served by a cached group program."""
    series = ctx.snapshot.get("program_cache_lookups_total", {}).get("series", [])
    counts = {}
    for s in series:
        result = s["labels"].get("result")
        counts[result] = counts.get(result, 0.0) + s["value"]
    total = sum(counts.values())
    if not total:
        return None
    return 100.0 * counts.get("hit", 0.0) / total
