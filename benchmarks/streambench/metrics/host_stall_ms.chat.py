"""Device idle time per engine step, in ms: the device idle seconds inside
the program's ``ss.step`` spans (one per ``PipeServeEngine._step``) that
start in the traced window, over the number of those spans.  Time the chip
waits on the host's own work in a step, between its programs."""


def read(ctx):
    steps = ctx.trace.spans_named("ss.step")
    if not steps:
        return None
    return 1e3 * sum(ctx.trace.idle_inside(s, e) for s, e in steps) / len(steps)
