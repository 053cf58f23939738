"""Share of the prefill programs' positions that fed no prompt token, by the
program's own counters: 1 - ``prefill_live_tokens`` / ``prefill_slot_tokens``
(positions computed: bucket rows x bucket length) in the traced window.
None where the program keeps no counters or ran no prefill."""


def read(ctx):
    c = ctx.counters
    if not c or not c.get("prefill_slot_tokens"):
        return None
    return 1.0 - c["prefill_live_tokens"] / c["prefill_slot_tokens"]
