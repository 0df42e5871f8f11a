"""``quant_matmul``'s share of its roofline inside the prefill program:
every call's least time over the prompt's real rows (not the bucket's
padding), as the family counts the calls, over the kernel's device
time."""
from bench import work
from bench.names import PREFILL, QUANT_MATMUL


def read(ctx):
    t = ctx.trace.op_s(QUANT_MATMUL, PREFILL)
    if t <= 0:
        return None
    least = sum(work.least_time(f, b, ctx.peak)
                for s in ctx.steps for n in s.prefills
                for f, b in ctx.family.plain.quant_matmul_calls(ctx.md, n, 1))
    return 100.0 * least / t
