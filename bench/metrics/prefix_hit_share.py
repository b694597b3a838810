"""Share of the prompt tokens admitted in the window that the prefix cache
served (the ``prefix_tokens_reused`` delta over those prompts' tokens), %.
A window that admitted prompts and reused none of them reads 0."""


def read(run):
    prompts = sum(len(run.drv.tracked[i].req.prompt)
                  for i in run.admitted_window)
    if not prompts:
        return None
    return 100.0 * run.window_delta["prefix_tokens_reused"] / prompts
