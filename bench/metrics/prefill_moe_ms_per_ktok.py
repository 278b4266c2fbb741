"""Model step, prefill: device self time of the ``moe`` scope (router and
experts) in ``jit_chunk`` per thousand prompt tokens of the engine's
``prefill_round`` spans in the window."""
from bench import scopes


def read(ctx):
    return scopes.per_unit(ctx, "jit_chunk", "moe", "prefill_round",
                           "tokens", 1e3)
