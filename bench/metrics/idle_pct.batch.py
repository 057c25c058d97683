"""Share of the traced window in which no operation ran on the device (sweep cells)."""
from benchlib import readers


def read(ctx):
    return readers.idle_pct(ctx)
