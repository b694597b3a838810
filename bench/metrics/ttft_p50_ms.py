"""Median time to first token, from each request's due time (ms)."""
from tails import percentile, ttft_s


def read(run):
    v = percentile(ttft_s(run), 50)
    return None if v is None else v * 1e3
