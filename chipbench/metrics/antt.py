"""Average normalized turnaround: the mean over the window's finished jobs
of wall turnaround over the job's own service time in the run (the sum of
its block spans, its prefill included). It needs no solo run."""

import numpy as np


def read(ctx):
    ratios = [r.turnaround / ctx.service_s(r.index) for r in ctx.jobs
              if r.finished is not None and ctx.service_s(r.index) > 0]
    return float(np.mean(ratios)) if ratios else None
