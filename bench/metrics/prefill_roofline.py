"""Prefill program vs chip: the least time the chip needs for the prefill
rounds of the traced window (max of FLOPs over peak and bytes over
bandwidth, per round; MoE counted for its top-k experts only) over the
device time of ``jit_chunk``."""


def read(ctx):
    r = ctx.reduced
    ns = r.program_ns("jit_chunk") if r is not None else 0.0
    if not ns or not ctx.loop.rounds:
        return None
    need = sum(ctx.arch.prefill_round(ctx.m, members).min_seconds(
        ctx.peaks.bf16_flops_per_s, ctx.peaks.hbm_bytes_per_s)
        for members in ctx.loop.rounds)
    return 100.0 * need / (ns / 1e9)
