"""Share of its roofline that the Pallas ``decode_attention`` kernel reached
in the traced window, in %: the least time the chip needs for the live work of
every call (live rows, each row's own cache length, the tokens it fed; the
family's calls per layer) over the kernel's device time in the trace."""
from sbench.flops import kernel_needs


def read(ctx):
    t = ctx.trace.kernel_s("decode_attention")
    if t <= 0 or not ctx.decode_calls:
        return None
    needs = kernel_needs(ctx.family.KERNELS["decode_attention"], ctx.cfg,
                         (rows for _, _, rows in ctx.decode_calls), ctx.peaks)
    return sum(100.0 * calls * need / t for calls, need in needs)
