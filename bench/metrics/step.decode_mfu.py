"""Whole decode step's share of the bf16 peak: model FLOPs of the live
slots' tokens decoded in the window, as the configuration's family
module counts them (``decode_step_flops``), over the decode programs'
device time times the peak (%).  Moves ``out_tok_s``."""


def read(ctx):
    t = ctx.program_s("decode")
    flops = ctx.work["decode_flops"]
    if not t or not flops:
        return None
    return 100.0 * flops / (t * ctx.peaks["bf16_flops"])
