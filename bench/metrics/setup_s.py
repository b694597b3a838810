"""Process start to window start: weights, programs, warm-up traffic (s)."""


def read(run):
    return run.setup_s
