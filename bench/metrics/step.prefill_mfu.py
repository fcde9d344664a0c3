"""Model FLOPs of the live prompt tokens prefilled in the window, as the
configuration's family module counts them (``prefill_flops``), over the
device time of the prefill programs times the chip's bf16 peak (%).
Masked rows of the (slots, chunk) program are not counted."""


def read(ctx):
    t = ctx.program_s("prefill")
    flops = ctx.work["prefill_flops"]
    if not t or not flops:
        return None
    return 100.0 * flops / (t * ctx.peaks["bf16_flops"])
