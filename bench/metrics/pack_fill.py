"""Share of the token budget the engine's packs filled over the window:
``packed_tokens`` delta over ``ragged_ticks`` delta times the budget, %."""


def read(run):
    d = run.window_delta
    steps = d["ragged_ticks"]
    budget = run.cell.settings["engine"]["token_budget"]
    return 100.0 * d["packed_tokens"] / (steps * budget) if steps else None
