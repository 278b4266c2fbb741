"""Whole prefill step vs chip peak: FLOPs needed for the prefill rounds of
the traced window over the device time of ``jit_chunk`` and
``jit_finalize`` x peak.  It bounds ``prefill_roofline`` from the step's
side: a kernel taken off the path leaves its roofline silent, not this."""


def read(ctx):
    r = ctx.reduced
    ns = (r.program_ns("jit_chunk") + r.program_ns("jit_finalize")) \
        if r is not None else 0.0
    if not ns or not ctx.loop.rounds:
        return None
    flops = sum(ctx.arch.prefill_round(ctx.m, members).flops
                for members in ctx.loop.rounds)
    return 100.0 * flops / (ns / 1e9 * ctx.peaks.bf16_flops_per_s)
