"""Model FLOPs the window's tokens needed (decode steps over their real
slots and live contexts, prefills over their real prompt rows) over the
window times the chip's bf16 peak; the FLOPs are the family's counts."""


def read(ctx):
    plain = ctx.family.plain
    flops = sum(plain.decode_flops(ctx.md, s.contexts)
                for s in ctx.steps if s.contexts)
    flops += sum(plain.prefill_flops(ctx.md, n)
                 for s in ctx.steps for n in s.prefills)
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace.window_s * ctx.peak["flops_per_s"])
