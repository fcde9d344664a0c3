"""90th percentile, over the requests due in the window, of the engine's
own queue wait: ``Request.admitted_at - Request.submitted_at``, both
stamped by ``ServingEngine`` on the host clock (ms).  A request never
admitted waits until the run ends, as in ``engine.queue_wait_p90_ms``.
Nothing to read where the engine stamps no requests.  Moves
``ttft_p90_ms``."""
import time

from bench.harness import metrics as M


def read(ctx):
    reqs = [r.engine_req for r in M.due_in_window(ctx.reqs, ctx.win)]
    if not reqs or any(getattr(e, "submitted_at", None) is None
                       for e in reqs):
        return None
    end = time.perf_counter()
    waits = [(e.admitted_at if e.admitted_at is not None else end)
             - e.submitted_at for e in reqs]
    return M.percentile(waits, 90) * 1e3
