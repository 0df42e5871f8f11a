"""Share of the window's device busy time spent in the prefill program.
The scheduler admits every free slot before a decode step, so this is how
long admissions hold decode back."""
from bench.names import PREFILL


def read(ctx):
    busy = ctx.trace.busy_s()
    if busy <= 0:
        return None
    return 100.0 * ctx.trace.op_s("", PREFILL) / busy
