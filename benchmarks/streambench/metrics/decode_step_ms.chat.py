"""Mean device time of one decode or verify program execution (the jitted
``_lane_decode``), from the profiler trace's XLA Modules line, in ms."""


def read(ctx):
    runs = ctx.trace.module_runs("_lane_decode")
    return 1e3 * sum(runs) / len(runs) if runs else None
