"""Decode program vs chip: the least time the chip needs for the ticks of
the traced window (bytes: weights once, MoE experts as the expected distinct
experts the tick's tokens route to, keys and values held by active slots)
over the device time of ``jit_tick_block``."""
from bench import counts


def read(ctx):
    r = ctx.reduced
    ns = r.program_ns("jit_tick_block") if r is not None else 0.0
    if not ns or not ctx.loop.blocks:
        return None
    need = sum(w.min_seconds(ctx.peaks.bf16_flops_per_s,
                             ctx.peaks.hbm_bytes_per_s)
               for block in ctx.loop.blocks
               for w in counts.decode_block(ctx.arch, ctx.m, block))
    return 100.0 * need / (ns / 1e9)
