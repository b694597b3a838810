"""Median time from admission to first token of the schedule's requests
whose first token falls in the window: the program's stamps
``first_token_at`` minus ``admitted_at`` of each request, which is the
chunked prefill beside the other slots' work (ms).  A program that does
not stamp its requests gives nothing."""
from tails import percentile


def read(run):
    lo, hi = run.window
    times = []
    for t in run.drv.tracked:
        r = t.request
        f = getattr(r, "first_token_at", None)
        if t.req.idx >= 0 and f is not None and lo < f <= hi:
            times.append(f - r.admitted_at)
    v = percentile(times, 50)
    return None if v is None else v * 1e3
