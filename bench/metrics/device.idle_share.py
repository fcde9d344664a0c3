"""Share of the traced window in which no operation ran on the device:
1 - (union of busy intervals) / window (%).  The engine's host work per
tick holds the chip back.  Moves ``out_tok_s``."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share
