"""Model FLOP/s utilization, in per cent, of the whole served step: the
operations of the jobs' own prefills and decode steps in blocks that ended
in the traced window, counted from the configuration's shapes, over the
window's seconds times the chip's bfloat16 peak. The warm-ups' discarded
work does not count."""


def read(ctx):
    if ctx.trace is None:
        return None
    counts, cfg = ctx.cell.counts(), ctx.cell.config
    flops = 0.0
    for b in ctx.recorder.blocks:
        if not ctx.in_window(b.end):
            continue
        if b.prefill:
            flops += counts.prefill(cfg, batch=ctx.batch,
                                    prompt_len=ctx.prompt_len)[0]
        for i in range(b.decode_steps):
            flops += counts.decode_step(
                cfg, batch=ctx.batch,
                filled=ctx.prompt_len + b.decode_start + i)[0]
    if flops == 0:
        return None
    return 100.0 * flops / (ctx.trace.window_s
                            * ctx.peaks()["bf16_flops_per_s"])
