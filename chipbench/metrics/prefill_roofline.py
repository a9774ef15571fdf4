"""Roofline share, in per cent, of the prefill (``_prefill``,
``lm.prefill``): its roofline time, counted from the configuration's
shapes, over the mean device time of an execution of ``jit__prefill`` in
the trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    ran = any(b.prefill and ctx.in_window(b.start)
              for b in ctx.recorder.blocks)
    ran = ran or any(ctx.in_window(w.start) for w in ctx.recorder.warmups)
    if not ran:
        return None
    flops, nbytes = ctx.cell.counts().prefill(
        ctx.cell.config, batch=ctx.batch, prompt_len=ctx.prompt_len)
    return 100.0 * (ctx.roofline_s(flops, nbytes)
            / ctx.trace.module_mean_s("jit__prefill"))
