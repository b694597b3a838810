"""95th percentile of every gap between consecutive output tokens of a
request, over all gaps that end in the window (ms)."""
from tails import gaps_s, percentile


def read(run):
    v = percentile(gaps_s(run), 95)
    return None if v is None else v * 1e3
