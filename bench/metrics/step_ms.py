"""Device time of the serve-step program per step in the traced window,
per device (ms)."""


def read(run):
    tr = run.trace
    if tr is None or not tr.steps:
        return None
    return tr.step_s / tr.steps * 1e3
