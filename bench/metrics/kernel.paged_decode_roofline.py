"""The paged-decode kernel's share of its roofline: least time the
attention needs over the summed device time of the kernel's operations
(%).  The needed FLOPs and bytes of each decode step, over the live
slots and every layer, are those the configuration's family module
defines (``decode_attention_need`` in ``bench/reference/<family>.py``).
The least time is the larger of bytes / HBM bandwidth and FLOPs / bf16
peak.  Moves ``out_tok_s``."""


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.kernel_s.get("paged_decode", 0.0)
    if not t or not ctx.work["kernel_bytes"]:
        return None
    least = max(ctx.work["kernel_bytes"] / ctx.peaks["hbm_bytes_per_s"],
                ctx.work["kernel_flops"] / ctx.peaks["bf16_flops"])
    return 100.0 * least / t
