"""Self time of the program's trace, lower and executable-fetch spans per design point (sweep cells)."""
from benchlib import build


def read(ctx):
    return build.build_ms_per_point(ctx)
