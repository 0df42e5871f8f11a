"""Device time per run of the prefill program, from the trace."""
from bench.names import PREFILL


def read(ctx):
    runs = ctx.trace.module_runs(PREFILL)
    if not runs:
        return None
    return 1e3 * ctx.trace.module_s(PREFILL) / runs
