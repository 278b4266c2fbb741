"""Model step, decode: device self time of the ``moe`` scope in
``jit_tick_block`` (the router over all experts, the held experts and the
shared experts) per decode tick of the engine's ``decode_block`` spans in
the window."""
from bench import scopes


def read(ctx):
    return scopes.per_unit(ctx, "jit_tick_block", "moe", "decode_block",
                           "ticks", 1.0)
