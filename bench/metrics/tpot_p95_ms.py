"""95th percentile, over requests with two or more deliveries in the window,
of (last delivery - first delivery) / tokens delivered after the first."""
import numpy as np


def read(ctx):
    t = ctx.loop.tpot_s()
    return float(np.percentile(t, 95) * 1e3) if t.size else None
