"""Roofline share, in per cent, of the decode step (``_decode``,
``lm.decode_step``): the mean roofline time of the decode steps that ran in
the traced window, counted from the configuration's shapes, over the mean
device time of an execution of ``jit__decode`` in the trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    counts = ctx.cell.counts()
    filled = []
    for b in ctx.recorder.blocks:
        if ctx.in_window(b.start):
            filled += [ctx.prompt_len + b.decode_start + i
                       for i in range(b.decode_steps)]
    filled += [ctx.prompt_len for w in ctx.recorder.warmups
               if ctx.in_window(w.start)]
    if not filled:
        return None
    roof = sum(ctx.roofline_s(*counts.decode_step(
        ctx.cell.config, batch=ctx.batch, filled=f)) for f in filled)
    return 100.0 * roof / len(filled) / ctx.trace.module_mean_s("jit__decode")
