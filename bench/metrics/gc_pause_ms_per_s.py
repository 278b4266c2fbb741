"""Host runtime: milliseconds of Python's collector (the engine's ``gc``
spans) that started in the window, per second of window."""
from bench import scopes


def read(ctx):
    spans = scopes.window_spans(ctx, "gc")
    if spans is None:
        return None
    t0, t1 = scopes.window(ctx)
    return sum(s.t1 - s.t0 for s in spans) * 1e3 / (t1 - t0)
