"""Wait from submission to the dispatch of the request's prefill, by the
program's own wall stamps (``Request.w_submit``, ``w_prefill_start``): 95th
percentile over the requests due in the traced window, in ms.  A request not
started by the traced window's close counts with its wait so far.  None
where the program does not stamp its requests."""
from sbench.harness import percentile


def read(ctx):
    waits = []
    for r in ctx.requests:
        submit = getattr(r, "w_submit", None)
        if submit is None:
            return None
        start = getattr(r, "w_prefill_start", None)
        waits.append((start if start is not None and start <= ctx.close else ctx.close) - submit)
    v = percentile(waits, 95)
    return None if v is None else v * 1e3
