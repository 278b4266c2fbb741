"""Engine scheduler: 95th percentile of admitted -> prefill finished
(``Request.t_prefilled - t_admit``, the engine's own stamps) over the
requests due in the window that were admitted, followed past its end: one
admitted but still prefilling when the loop ends counts at its wait so
far."""
import numpy as np


def read(ctx):
    loop = ctx.loop
    waits = []
    for r in loop.reqs:
        if r.rid not in loop.offsets or r.rejected:
            continue
        t_admit = getattr(r, "t_admit", None)
        if t_admit is None:
            continue
        done = r.t_prefilled if r.t_prefilled is not None else loop.t_stop
        waits.append(done - t_admit)
    return float(np.percentile(waits, 95) * 1e3) if waits else None
