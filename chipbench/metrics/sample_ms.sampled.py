"""Device time per step of the device sampler, in ms, per chip: every device
op in the window created under ``repro/sampling/device_graph.py:
DeviceSampler.sample_blocks_stats`` (the sampling kernels, the relabel, the
block packing), by the op's creating stack in the program's HLO."""
from chipbench.lib import trace


def read(view):
    spent = view.seconds(lambda op: trace.in_stack(
        op, "repro/sampling/device_graph.py",
        "DeviceSampler.sample_blocks_stats"))
    if spent <= 0 or not view.work.get("steps"):
        return None
    return 1000.0 * spent / view.work["steps"]
