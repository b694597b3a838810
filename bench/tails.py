"""Samples and percentiles of the end-to-end metrics, over the window.

Every sample the window holds counts: a request due in the window with no
first token by its close counts as close minus due, so a stall raises the
tail instead of dropping samples; a request the engine refused or failed
counts the same way.  Percentiles are by nearest rank.
"""
from __future__ import annotations

import math
from typing import List, Optional


def percentile(xs: List[float], p: float) -> Optional[float]:
    if not xs:
        return None
    s = sorted(xs)
    return s[max(0, math.ceil(p / 100 * len(s)) - 1)]


def ttft_s(run) -> List[float]:
    lo, hi = run.window
    out = []
    for t in run.drv.tracked:
        if t.req.due is None or not lo <= t.due < hi:
            continue
        ok = t.times and t.times[0] <= hi and not t.failed
        out.append((t.times[0] if ok else hi) - t.due)
    out += [hi - (run.origin + r.due) for r in run.drv.refused
            if r.due is not None and lo <= run.origin + r.due < hi]
    return out


def gaps_s(run) -> List[float]:
    """Every gap between consecutive output tokens of a request that ends
    inside the window."""
    lo, hi = run.window
    out = []
    for t in run.drv.tracked:
        ts = t.times
        out += [b - a for a, b in zip(ts, ts[1:]) if lo < b <= hi]
    return out


def tokens_in_window(run) -> int:
    lo, hi = run.window
    return sum(1 for t in run.drv.tracked for x in t.times if lo < x <= hi)
