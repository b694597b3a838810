"""Median wait in the engine's queue of the schedule's requests admitted in
the window: the program's stamps ``admitted_at`` minus ``submitted_at`` of
each request (ms).  A program that does not stamp its requests gives
nothing."""
from tails import percentile


def read(run):
    lo, hi = run.window
    waits = []
    for t in run.drv.tracked:
        r = t.request
        a = getattr(r, "admitted_at", None)
        if t.req.idx >= 0 and a is not None and lo <= a < hi:
            waits.append(a - r.submitted_at)
    v = percentile(waits, 50)
    return None if v is None else v * 1e3
