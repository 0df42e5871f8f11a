"""The grouped expert kernel's share of its roofline inside the prefill
program: every call's least time over each admission's real prompt rows
(not the bucket's padding), as the family counts the calls, over the
kernel's device time."""
from bench import work
from bench.names import PREFILL

EXPERT_MATMUL = "expert_matmul_b"       # kernels/expert_matmul.py name=


def read(ctx):
    calls = getattr(ctx.family.plain, "expert_matmul_calls", None)
    t = ctx.trace.op_s(EXPERT_MATMUL, PREFILL)
    if calls is None or t <= 0:
        return None
    least = sum(work.least_time(f, b, ctx.peak)
                for s in ctx.steps for n in s.prefills
                for f, b in calls(ctx.md, n))
    return 100.0 * least / t
