"""90th percentile, over the requests due in the window, of the time from
each request's due time to the start of the engine tick that admitted it
(host clock, ms).  A request never admitted waits until the run ends."""
import time

from bench.harness import metrics as M


def read(ctx):
    reqs = M.due_in_window(ctx.reqs, ctx.win)
    if not reqs:
        return None
    end = time.perf_counter()
    waits = [((r.admitted_tick_start if r.admitted_tick_start is not None
               else end) - r.due_at) for r in reqs]
    return M.percentile(waits, 90) * 1e3
