"""Median wait from submission to admission of the requests admitted in
the window: the engine tracer's ``submit`` and ``admit`` instants."""
import numpy as np


def read(ctx):
    if not ctx.queue_waits:
        return None
    return float(np.percentile(ctx.queue_waits, 50)) * 1e3
