"""Whole-step share of the chip's bf16 peak in sampled training, in %:
operations per real seed (``lib/counts.sage_step_flops`` over the real rows
and edges of the checked steps' blocks, all shards, divided by their real
seeds) times the real seeds stepped in the traced window, over the window's
length, the chips and the peak."""


def read(view):
    w = view.work
    if view.window_s <= 0 or not w.get("seeds"):
        return None
    return 100.0 * w["flops_per_seed"] * w["seeds"] / (
        view.window_s * view.chips * view.peaks["bf16_flops"])
