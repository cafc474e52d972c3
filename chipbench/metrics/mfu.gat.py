"""Whole-step share of the chip's bf16 peak in full-batch GAT training, in %:
the operations one epoch requires (``lib/gat_counts.gat_epoch_flops``:
projections and node scores forward and backward, and 2·nnz·K for each of
the three passes over the entries of every layer) times the epochs in the
traced window, over the window's length, the chips and the peak."""


def read(view):
    w = view.work
    if view.window_s <= 0 or not w.get("steps"):
        return None
    flops = w["flops_per_step"] * w["steps"]
    return 100.0 * flops / (view.window_s * view.chips
                            * view.peaks["bf16_flops"])
