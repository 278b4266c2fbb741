"""Device: share of the traced window in which no operation ran."""


def read(ctx):
    r = ctx.reduced
    if r is None or not r.window_ns:
        return None
    return 100.0 * (1.0 - r.busy_ns / r.window_ns)
