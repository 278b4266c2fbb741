"""Model step, decode: device time of ``jit_tick_block`` in the traced window
per decode tick dispatched there."""


def read(ctx):
    r = ctx.reduced
    ticks = len(ctx.loop.blocks) * ctx.drain_every
    ns = r.program_ns("jit_tick_block") if r is not None else 0.0
    return ns / 1e6 / ticks if ns and ticks else None
