"""Share of group-program look-ups served from the program's cache (sweep cells)."""
from benchlib import program_cache


def read(ctx):
    return program_cache.hit_pct(ctx)
