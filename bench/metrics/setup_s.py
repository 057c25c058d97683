"""Set-up time: process start to the first call of the window, on the host clock."""
from benchlib import readers


def read(ctx):
    return ctx.setup_s
