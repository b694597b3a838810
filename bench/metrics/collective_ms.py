"""Device time of collective ops per step in the traced window, per chip
(ms); the part that overlapped no other op is noted beside it."""


def read(run):
    tr = run.trace
    if tr is None or not tr.steps or not tr.collective_s:
        return None
    run.notes.append(f"collective_ms: exposed (no op beside it) "
                     f"{tr.collective_exposed_s / tr.steps * 1e3} ms per step")
    return tr.collective_s / tr.steps * 1e3
