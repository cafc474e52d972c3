"""Share of its roofline reached by the gather-SDDMM kernel in full-batch
GAT training, in %.

Least time: for each layer's SDDMM of an epoch (the attention weights'
gradient over the stored entries), the larger of its operations over the
bf16 peak and its compulsory bytes over the HBM peak (``lib/gat_counts.
pass_compulsory``), times the epochs in the window. Time: the Pallas
kernel calls (``custom-call`` ops) in the window created under the
program's ``attention`` stage, by the op's creating stack in the
program's HLO. A program without the stage, or without a kernel there,
reads nothing."""
from chipbench.lib import counts, trace


def read(view):
    spent = view.seconds(lambda op: op.opcode == "custom-call"
                         and trace.in_stack(op, "repro/obs/stages.py",
                                            "attention"))
    calls = view.work.get("sddmm_calls_per_step")
    if spent <= 0 or not calls:
        return None
    least = sum(counts.least_time(f, b, view.peaks) for f, b in calls)
    return 100.0 * least * view.work["steps"] / spent
