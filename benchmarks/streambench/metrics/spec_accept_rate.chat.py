"""SpecuStream acceptance: draft tokens accepted per draft token proposed,
(tokens emitted in verify row-steps - verify row-steps) / sum of the rows'
speculation depths, from the tokens harvested after each step of the traced
window.  None when no verify step ran."""


def read(ctx):
    rows, emitted, depth = ctx.spec
    if depth <= 0:
        return None
    return (emitted - rows) / depth
