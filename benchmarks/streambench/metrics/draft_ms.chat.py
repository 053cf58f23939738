"""Mean host time of one draft proposal, in ms: the duration of the
program's ``ss.draft`` spans (``draft.propose``, verify steps only) that
start in the traced window."""


def read(ctx):
    spans = ctx.trace.spans_named("ss.draft")
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) / 1e6
