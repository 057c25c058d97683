"""JAX traces per request, from the program's jit_traces_total counter (loop cells)."""
from benchlib import build


def read(ctx):
    return build.jit_traces_per_point(ctx)
