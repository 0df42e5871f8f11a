"""The fused paged-attention kernel's share of its roofline at decode:
the least time its needed work takes (live pages and live context of the
slots that hold a request, in every layer that calls the kernel, as the
family counts them) over its device time."""
from bench import work
from bench.names import DECODE, PAGED_ATTENTION


def read(ctx):
    t = ctx.trace.op_s(PAGED_ATTENTION, DECODE)
    if t <= 0:
        return None
    least = sum(work.least_time(f, b, ctx.peak)
                for s in ctx.steps if s.contexts
                for f, b in ctx.family.plain.attention_calls(
                    ctx.md, s.contexts, ctx.serving))
    return 100.0 * least / t
