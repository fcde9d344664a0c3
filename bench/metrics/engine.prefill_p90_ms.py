"""90th percentile, over the requests the engine admitted in the window,
of ``Request.prefilled_at - Request.admitted_at``: from the request
leaving the queue to its first token on the host, both stamped by
``ServingEngine`` on the host clock (ms).  That is its slot reset, its
own prefill chunks and the readback of its first token, and none of the
queue wait.  Nothing to read where the engine stamps no requests.
Moves ``ttft_p90_ms``."""
from bench.harness import metrics as M


def read(ctx):
    spans = []
    for r in ctx.reqs:
        e = r.engine_req
        at = getattr(e, "admitted_at", None)
        if at is not None and ctx.win.open <= at <= ctx.win.end:
            spans.append(e.prefilled_at - at)
    if not spans:
        return None
    return M.percentile(spans, 90) * 1e3
