"""Error of the structural predictor after one block, in per cent: over
the jobs of two or more blocks whose first block starts in the window and
whose every block was recorded, the mean of |Eq. 2's total
(``predictor.total_s`` at one block done) - the job's service time (the sum
of its ``executor.block`` spans)| over that service time."""

from collections import defaultdict

import numpy as np

from chipbench import program


def read(ctx):
    spans = defaultdict(list)
    for r in program.records("executor.block"):
        spans[r.args["job"]].append(r)
    errors = []
    for c in program.records("predictor.total_s"):
        job, blocks = spans[c.args["job"]], c.args["blocks"]
        if (c.args["done"] != 1 or blocks < 2 or len(job) != blocks
                or not ctx.in_window(job[0].start)):
            continue
        service = sum(s.end - s.start for s in job)
        errors.append(abs(c.args["value"] - service) / service)
    return 100.0 * float(np.mean(errors)) if errors else None
