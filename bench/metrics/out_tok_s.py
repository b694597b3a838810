"""Output tokens emitted in the window over the window's length."""
from tails import tokens_in_window


def read(run):
    lo, hi = run.window
    n = tokens_in_window(run)
    return n / (hi - lo) if n else None
