"""Mean over the window's jobs of the time from the end of a job's arrival
warm-up to the start of its first block: the wait the scheduling core
imposes."""

import numpy as np


def read(ctx):
    warm = {w.job: w.end for w in ctx.recorder.warmups}
    first = {}
    for b in ctx.recorder.blocks:
        first.setdefault(b.job, b.start)
    waits = [first[r.index] - warm[r.index] for r in ctx.jobs
             if r.index in warm and r.index in first
             and ctx.in_window(r.submitted)]
    return float(np.mean(waits)) if waits else None
