"""95th percentile of due -> first token delivered, over every request due
in the window (one still waiting at the end counts at its wait so far)."""
import numpy as np


def read(ctx):
    t = ctx.loop.ttft_s()
    return float(np.percentile(t, 95) * 1e3) if t.size else None
