"""Share of its roofline that the Pallas ``decode_attention`` kernel reached
in the traced window, in %: the least time the chip needs for the live work of
every call (live rows, each row's own cache length, the tokens it fed; one
call per layer) over the kernel's device time in the trace."""
from sbench.flops import decode_attention_cost, least_time
from sbench.weights import Dims


def read(ctx):
    t = ctx.trace.kernel_s("decode_attention")
    if t <= 0 or not ctx.decode_calls:
        return None
    m = Dims.of(ctx.cfg)
    pf, bw = ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    need = sum(least_time(*decode_attention_cost(m, rows), pf, bw)
               for _, _, rows in ctx.decode_calls)
    return 100.0 * m.n_layers * need / t
