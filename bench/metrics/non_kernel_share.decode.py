"""Share of the decode program's device op time outside the two Pallas
kernels: the pool copy, the column permutes, norms, rotary embedding,
the tied head, sampling."""
from bench.names import DECODE, PAGED_ATTENTION, QUANT_MATMUL


def read(ctx):
    total = ctx.trace.op_s("", DECODE)
    if total <= 0:
        return None
    kernels = (ctx.trace.op_s(QUANT_MATMUL, DECODE)
               + ctx.trace.op_s(PAGED_ATTENTION, DECODE))
    return 100.0 * (total - kernels) / total
