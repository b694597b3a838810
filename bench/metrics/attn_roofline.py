"""Attention kernel's share of its roofline in the traced window: the least
time its work needs (the larger of its FLOPs over the bf16 peak and its
bytes over HBM bandwidth, the work split over the chips) over the kernel's
device time per chip, %."""


def read(run):
    tr, w = run.trace, run.traced_work
    if tr is None or w is None or not tr.kernel_s or not w.attn_flops:
        return None
    t_flops = w.attn_flops / (run.chips * run.peaks["bf16_flops"])
    t_bytes = w.attn_bytes / (run.chips * run.peaks["hbm_bytes_per_s"])
    run.notes.append(f"attn_roofline: bound by "
                     f"{'bytes' if t_bytes >= t_flops else 'FLOPs'} "
                     f"(least time {max(t_flops, t_bytes)} s, kernel "
                     f"{tr.kernel_s} s per chip)")
    return 100.0 * max(t_flops, t_bytes) / tr.kernel_s
