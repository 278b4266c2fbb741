"""Model step, decode: device self time of the ``attn`` scope in
``jit_tick_block`` (projections, the paged gather and the attention
itself; not the token's write into the pool) per decode tick of the
engine's ``decode_block`` spans in the window."""
from bench import scopes


def read(ctx):
    return scopes.per_unit(ctx, "jit_tick_block", "attn", "decode_block",
                           "ticks", 1.0)
