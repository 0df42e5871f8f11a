"""Share of the traced window in which no operation ran on the device
while the innermost open program span was the wait for a program's
sampled tokens (``fetch``, ``fetch.ready``, ``fetch.to_host``).  None
where the program opens no such spans."""
from bench import scopes


def read(ctx):
    if not any(e.name == "fetch" for e in ctx.trace.host):
        return None
    return scopes.idle_share(
        ctx.trace, lambda open_: bool(open_) and open_[0] in scopes.FETCH)
