"""Host time blocked on the chip after the solves, per design point (sweep cells)."""
from benchlib import build


def read(ctx):
    return build.device_wait_ms_per_point(ctx)
