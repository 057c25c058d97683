"""Median time from sending a request to its result on the host, over all requests."""
import numpy as np

from benchlib import readers


def read(ctx):
    return float(np.percentile(readers.latencies_ms(ctx), 50))
