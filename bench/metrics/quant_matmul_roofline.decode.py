"""``quant_matmul``'s share of its roofline inside the decode program:
every call's least time over its real rows (the slots that hold a
request) and wire bytes, as the family counts the calls, over the
kernel's device time."""
from bench import work
from bench.names import DECODE, QUANT_MATMUL


def read(ctx):
    t = ctx.trace.op_s(QUANT_MATMUL, DECODE)
    if t <= 0:
        return None
    least = sum(work.least_time(f, b, ctx.peak)
                for s in ctx.steps if s.contexts
                for f, b in ctx.family.plain.quant_matmul_calls(
                    ctx.md, len(s.contexts), len(s.contexts)))
    return 100.0 * least / t
