"""Whole-step model FLOP utilisation over the traced window, in %: the FLOPs
the decode, verify and prefill calls needed (live rows and fed tokens only)
over device busy time times the chip's peak bf16 FLOP/s."""


def read(ctx):
    busy = ctx.trace.busy_s
    if busy <= 0 or not (ctx.decode_calls or ctx.prefill_calls):
        return None
    fam, cfg = ctx.family, ctx.cfg
    flops = sum(fam.decode_step_flops(cfg, rows) for _, _, rows in ctx.decode_calls)
    flops += sum(fam.prefill_flops(cfg, lens) for lens in ctx.prefill_calls)
    return 100.0 * flops / (busy * ctx.peaks["bf16_flops_per_s"])
