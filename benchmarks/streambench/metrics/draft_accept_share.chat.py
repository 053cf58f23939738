"""SpecuStream acceptance by the program's own counters: draft tokens
accepted over draft tokens proposed (``spec_accepted`` / ``spec_proposed``,
active rows of verify steps) in the traced window.  None where the program
keeps no counters or no verify step ran."""


def read(ctx):
    c = ctx.counters
    if not c or not c.get("spec_proposed"):
        return None
    return c["spec_accepted"] / c["spec_proposed"]
