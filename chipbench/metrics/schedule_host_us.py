"""Host time of the scheduling core per block, in microseconds: the
``executor.schedule`` spans (``core.decide`` and the ``core.post`` of each
arrival, block start, block end and job end, predictor and policy hooks
included) that start in the window, over the blocks that start in it."""

from chipbench import program


def read(ctx):
    schedule = sum(r.end - r.start
                   for r in program.records("executor.schedule")
                   if ctx.in_window(r.start))
    blocks = sum(1 for r in program.records("executor.block")
                 if ctx.in_window(r.start))
    return 1e6 * schedule / blocks if blocks else None
