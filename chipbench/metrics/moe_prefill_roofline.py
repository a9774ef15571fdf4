"""Roofline share, in per cent, of the expert layers in a prefill: their
roofline time, counted from the configuration's shapes (``moe_prefill`` of
the step kind's counts), over the mean device time, per execution of
``jit__prefill``, of the operations under the program's
``jax.named_scope("moe")``. Nothing where the program opens no such
scope."""


def read(ctx):
    if ctx.trace is None:
        return None
    scope_s = ctx.trace.scope_s("jit__prefill", "moe")
    if scope_s == 0:
        return None
    flops, nbytes = ctx.cell.counts().moe_prefill(
        ctx.cell.config, batch=ctx.batch, prompt_len=ctx.prompt_len)
    return 100.0 * ctx.roofline_s(flops, nbytes) / scope_s
