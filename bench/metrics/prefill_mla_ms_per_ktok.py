"""Model step, prefill: device self time of the ``mla`` scope in
``jit_chunk`` (latent attention of each chunk over the slot's latents and
its own) per thousand prompt tokens of the engine's ``prefill_round`` spans
in the window."""
from bench import scopes


def read(ctx):
    return scopes.per_unit(ctx, "jit_chunk", "mla", "prefill_round",
                           "tokens", 1e3)
