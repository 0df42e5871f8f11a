"""Share of the decode program's device op time in the grouped expert
kernel: how much of a step the routed experts take."""
from bench.names import DECODE

EXPERT_MATMUL = "expert_matmul_b"       # kernels/expert_matmul.py name=


def read(ctx):
    total = ctx.trace.op_s("", DECODE)
    t = ctx.trace.op_s(EXPERT_MATMUL, DECODE)
    if total <= 0 or t <= 0:
        return None
    return 100.0 * t / total
