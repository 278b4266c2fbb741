"""Model step, decode: device self time of the ``kv_pool`` scope in
``jit_tick_block`` (the token's write into the page pool and each layer's
slice read out of the stacked pools and written back) per decode tick of
the engine's ``decode_block`` spans in the window."""
from bench import scopes


def read(ctx):
    return scopes.per_unit(ctx, "jit_tick_block", "kv_pool", "decode_block",
                           "ticks", 1.0)
