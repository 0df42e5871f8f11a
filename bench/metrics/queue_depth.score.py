"""Requests due and not yet answered, averaged over the window's steps
(the harness's own record of due times and first tokens)."""


def read(ctx):
    if not ctx.steps:
        return None
    return sum(s.queue for s in ctx.steps) / len(ctx.steps)
