"""Share of the decode program's leaf-op device time under the
``kv_write`` scope: the scatter of each step's K/V into its pages and
what XLA adds around it.  Reads the scoped reduction (``bench/scopes.py``);
None on ``bench/trace.py``'s, which keeps no scopes."""
from bench.names import DECODE


def read(ctx):
    if not hasattr(ctx.trace, "scope_s"):
        return None
    total = ctx.trace.op_s("", DECODE)
    if total <= 0:
        return None
    return 100.0 * ctx.trace.scope_s("kv_write", DECODE) / total
