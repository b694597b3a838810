"""Whole step's share of the chips' bf16 peak in the traced window: the
model FLOPs of the tokens served there (matrix products from the
configuration's shapes, attention from each token's real context length)
over window times chips times peak, %."""


def read(run):
    tr, w = run.trace, run.traced_work
    if tr is None or w is None or not w.tokens:
        return None
    flops = w.matmul_flops + w.attn_flops
    return 100.0 * flops / (tr.window_s * run.chips * run.peaks["bf16_flops"])
