"""Roofline share, in per cent, of latent attention in a decode step: the
mean roofline time of the decode steps that ran in the traced window,
counted from the configuration's shapes (``mla_decode`` of the step kind's
counts, over each step's filled positions, as ``decode_roofline`` takes
them), over the mean device time, per execution of ``jit__decode``, of the
operations under the program's ``jax.named_scope("mla")``. Nothing where
the program opens no such scope."""


def read(ctx):
    if ctx.trace is None:
        return None
    filled = []
    for b in ctx.recorder.blocks:
        if ctx.in_window(b.start):
            filled += [ctx.prompt_len + b.decode_start + i
                       for i in range(b.decode_steps)]
    filled += [ctx.prompt_len for w in ctx.recorder.warmups
               if ctx.in_window(w.start)]
    if not filled:
        return None
    scope_s = ctx.trace.scope_s("jit__decode", "mla")
    if scope_s == 0:
        return None
    counts = ctx.cell.counts()
    roof = sum(ctx.roofline_s(*counts.mla_decode(
        ctx.cell.config, batch=ctx.batch, filled=f)) for f in filled)
    return 100.0 * roof / len(filled) / scope_s
