"""Time from due to the start of the step that admitted the request (the
program's prefill-start tick mapped to the host clock): 95th percentile over
the requests due in the traced window, in seconds.  Still-waiting requests
count with their wait so far."""
from sbench.harness import percentile


def read(ctx):
    return percentile(ctx.host["qwait"], 95)
