"""Share of the traced window in which no operation ran on the device
while the host was inside the scheduler's ``step`` and not in a fetch of
tokens: dispatch, admission bookkeeping, emitting tokens.  None where the
program opens no ``step`` span."""
from bench import scopes


def read(ctx):
    if not any(e.name == "step" for e in ctx.trace.host):
        return None
    return scopes.idle_share(
        ctx.trace, lambda open_: "step" in open_
        and not any(n in scopes.FETCH for n in open_))
