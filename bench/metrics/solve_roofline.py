"""Circuit solve's share of the chip's roofline: counted work over device busy time."""
from benchlib import readers


def read(ctx):
    return readers.solve_roofline(ctx)
