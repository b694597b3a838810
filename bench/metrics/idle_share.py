"""Share of the traced window in which no op ran on the device, averaged
over the chips, %."""


def read(run):
    tr = run.trace
    if tr is None or not tr.window_s:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
