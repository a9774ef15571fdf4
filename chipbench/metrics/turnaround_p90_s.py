"""90th percentile of job turnaround, from due time to result, in seconds.

Every job due in the window counts, those drained after it too. A job with
no result within the drain counts at the time it was given up, a lower
bound (it is also counted as failed). Linear interpolation between ranks.
"""

import numpy as np


def read(ctx):
    times = [r.ended - r.due for r in ctx.jobs if r.ended is not None]
    if not times:
        return None
    return float(np.percentile(times, 90))
