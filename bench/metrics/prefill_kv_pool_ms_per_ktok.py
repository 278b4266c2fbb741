"""Model step, prefill: device self time of the ``kv_pool`` scope in
``jit_chunk`` (the gather of the slots' dense view out of the page pool,
its scatter back, and the layer slices of the stacked pools) per thousand
prompt tokens of the engine's ``prefill_round`` spans in the window."""
from bench import scopes


def read(ctx):
    return scopes.per_unit(ctx, "jit_chunk", "kv_pool", "prefill_round",
                           "tokens", 1e3)
