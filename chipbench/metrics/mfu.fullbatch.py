"""Whole-step share of the chip's bf16 peak in full-batch training, in %:
the operations one epoch requires (``lib/counts.gcn_epoch_flops``: dense
layers forward and backward, 2·nnz·K for each aggregation and its
transpose) times the epochs in the traced window, over the window's length,
the chips and the peak."""


def read(view):
    w = view.work
    if view.window_s <= 0 or not w.get("steps"):
        return None
    flops = w["flops_per_step"] * w["steps"]
    return 100.0 * flops / (view.window_s * view.chips
                            * view.peaks["bf16_flops"])
