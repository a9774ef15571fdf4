"""Mean over the window's jobs of the service's hand-off
(``service.handoff``): from ``SchedulerService.submit`` to the moment the
service's driver turns to the job, which is the wait behind the block
running then and behind the warm-ups of the jobs taken before it."""

import numpy as np

from chipbench import program


def read(ctx):
    waits = [r.end - r.start for r in program.records("service.handoff")
             if ctx.in_window(r.start)]
    return float(np.mean(waits)) if waits else None
