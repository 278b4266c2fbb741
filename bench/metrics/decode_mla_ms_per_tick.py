"""Model step, decode: device self time of the ``mla`` scope in
``jit_tick_block`` (latent attention: the query's low-rank path and
absorption, the latent and rotary key of the new token, the scores and
weighted latents over the slot's latent cache, the output projection) per
decode tick of the engine's ``decode_block`` spans in the window."""
from bench import scopes


def read(ctx):
    return scopes.per_unit(ctx, "jit_tick_block", "mla", "decode_block",
                           "ticks", 1.0)
