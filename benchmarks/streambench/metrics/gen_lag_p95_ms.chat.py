"""How late the load generator submitted requests: 95th percentile of
(submit time - due time) over the requests due in the traced window, in ms
(host clock).  A starved generator shows here, not as a fast server."""
from sbench.harness import percentile


def read(ctx):
    v = percentile(ctx.host["lag"], 95)
    return None if v is None else v * 1e3
