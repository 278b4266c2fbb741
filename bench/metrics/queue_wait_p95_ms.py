"""Engine scheduler: 95th percentile of due -> admitted to a slot, over every
request due in the window (one still queued at the end counts at its wait so
far).  Read from the adapter's stamps."""
import numpy as np


def read(ctx):
    t = ctx.loop.queue_wait_s()
    return float(np.percentile(t, 95) * 1e3) if t.size else None
