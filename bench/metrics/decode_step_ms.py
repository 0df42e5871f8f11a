"""Device time per run of the decode program, from the trace."""
from bench.names import DECODE


def read(ctx):
    runs = ctx.trace.module_runs(DECODE)
    if not runs:
        return None
    return 1e3 * ctx.trace.module_s(DECODE) / runs
