"""Host self time of the engine and evaluation spans per request (loop cells)."""
from benchlib import readers


def read(ctx):
    return readers.host_ms_per_point(ctx)
