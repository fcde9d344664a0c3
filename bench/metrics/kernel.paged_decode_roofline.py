"""The paged-decode kernel's share of its roofline: least time the
attention needs over the summed device time of the kernel's operations
(%).  Needed: each live slot's K and V pages up to its position, its
query and its output, per layer; FLOPs of scores and weighted values
over its own keys.  The least time is the larger of bytes / HBM
bandwidth and FLOPs / bf16 peak (the bytes bound it at these shapes).
Moves ``out_tok_s``."""


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.kernel_s.get("paged_decode", 0.0)
    if not t or not ctx.work["kernel_bytes"]:
        return None
    least = max(ctx.work["kernel_bytes"] / ctx.peaks["hbm_bytes_per_s"],
                ctx.work["kernel_flops"] / ctx.peaks["bf16_flops"])
    return 100.0 * least / t
