"""Self time of the program's sample_trials span per Monte-Carlo trial: drawing
variation, stuck faults and the read-noise key ahead of the solve."""
from benchlib import readers


def read(ctx):
    return readers.self_ms_per_point(ctx, ("sample_trials",))
