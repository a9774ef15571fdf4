"""Mean span of the warm-up that the executor runs for every arriving job
(``LaneExecutor.add_job``: a prefill and a decode step, thrown away)."""

import numpy as np


def read(ctx):
    spans = [w.end - w.start for w in ctx.recorder.warmups
             if ctx.in_window(w.start)]
    return float(np.mean(spans)) if spans else None
