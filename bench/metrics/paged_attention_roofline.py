"""The fused paged-attention kernel's share of its roofline at decode:
the least time its needed work takes (live pages and live context of the
slots that hold a request) over its device time."""
from bench import work
from bench.names import DECODE, PAGED_ATTENTION


def read(ctx):
    t = ctx.trace.op_s(PAGED_ATTENTION, DECODE)
    if t <= 0:
        return None
    least = sum(work.least_time(*work.attention(ctx.md, s.contexts,
                                                ctx.serving), ctx.peak)
                for s in ctx.steps if s.contexts)
    return 100.0 * least * ctx.md["layers"] / t
