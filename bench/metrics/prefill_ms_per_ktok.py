"""Model step, prefill: device time of ``jit_chunk`` and ``jit_finalize`` in
the traced window per thousand prompt tokens prefilled there."""


def read(ctx):
    r = ctx.reduced
    tokens = sum(c for members in ctx.loop.rounds for _, c, _ in members)
    if r is None or not tokens:
        return None
    ns = r.program_ns("jit_chunk") + r.program_ns("jit_finalize")
    return ns / 1e6 / (tokens / 1e3) if ns else None
