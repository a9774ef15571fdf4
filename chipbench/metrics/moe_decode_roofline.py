"""Roofline share, in per cent, of the expert layers in a decode step: their
roofline time, counted from the configuration's shapes (``moe_decode`` of
the step kind's counts: the router, the routed experts that the batch's
tokens touch and the shared experts), over the mean device time, per
execution of ``jit__decode``, of the operations under the program's
``jax.named_scope("moe")``. Nothing where the program opens no such
scope."""


def read(ctx):
    if ctx.trace is None:
        return None
    scope_s = ctx.trace.scope_s("jit__decode", "moe")
    if scope_s == 0:
        return None
    flops, nbytes = ctx.cell.counts().moe_decode(ctx.cell.config,
                                                 batch=ctx.batch)
    return 100.0 * ctx.roofline_s(flops, nbytes) / scope_s
