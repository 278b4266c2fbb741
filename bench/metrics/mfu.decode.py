"""Whole decode step vs chip peak: FLOPs needed for the ticks of the traced
window over the device time of ``jit_tick_block`` x peak."""
from bench import counts


def read(ctx):
    r = ctx.reduced
    ns = r.program_ns("jit_tick_block") if r is not None else 0.0
    if not ns or not ctx.loop.blocks:
        return None
    flops = sum(w.flops for block in ctx.loop.blocks
                for w in counts.decode_block(ctx.arch, ctx.m, block))
    return 100.0 * flops / (ns / 1e9 * ctx.peaks.bf16_flops_per_s)
