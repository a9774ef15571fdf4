"""Share, in per cent, of the window's wall time in which a job is in the
system (from its submission to its result) and neither a block nor a
warm-up runs: the time the executor and the service's driver take between
spans."""

def _union(intervals):
    total, end = 0.0, None
    start = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            if end is not None:
                total += end - start
            start, end = a, b
        else:
            end = max(end, b)
    if end is not None:
        total += end - start
    return total


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def read(ctx):
    lo, hi = ctx.t0, ctx.t1
    in_system = _clip([(r.submitted, r.ended) for r in ctx.jobs
                       if r.submitted is not None and r.ended is not None],
                      lo, hi)
    if not in_system:
        return None
    spans = [(s.start, s.end) for s in ctx.recorder.blocks]
    spans += [(s.start, s.end) for s in ctx.recorder.warmups]
    busy = _union(in_system)
    # Spans run only while their job is in the system, so the part of the
    # busy time they cover is the union of their clipped intervals.
    covered = _union(_clip(spans, lo, hi))
    return 100.0 * max(0.0, busy - covered) / (hi - lo)
