"""Host self time of the engine and evaluation spans per design point (sweep cells)."""
from benchlib import readers


def read(ctx):
    return readers.host_ms_per_point(ctx)
