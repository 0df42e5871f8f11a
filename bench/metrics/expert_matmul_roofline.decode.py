"""The grouped expert kernel's share of its roofline inside the decode
program: every call's least time over the routed rows of the slots that
hold a request (the family's ``expert_matmul_calls``: those rows' FLOPs,
the packed bytes of the experts expected to be hit), over the kernel's
device time."""
from bench import work
from bench.names import DECODE

EXPERT_MATMUL = "expert_matmul_b"       # kernels/expert_matmul.py name=


def read(ctx):
    calls = getattr(ctx.family.plain, "expert_matmul_calls", None)
    t = ctx.trace.op_s(EXPERT_MATMUL, DECODE)
    if calls is None or t <= 0:
        return None
    least = sum(work.least_time(f, b, ctx.peak)
                for s in ctx.steps if s.contexts
                for f, b in calls(ctx.md, len(s.contexts)))
    return 100.0 * least / t
