"""Mean span of the blocks that hold no prefill: a job's second block on."""

import numpy as np


def read(ctx):
    spans = [b.end - b.start for b in ctx.recorder.blocks
             if not b.prefill and ctx.in_window(b.end)]
    return float(np.mean(spans)) if spans else None
