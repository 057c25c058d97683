"""Design-point evaluations (one point on one input) completed per second of the window."""
from benchlib import readers


def read(ctx):
    return readers.points_done(ctx) * ctx.traffic.n_samples / ctx.window_s
