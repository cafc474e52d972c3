"""Share of its roofline reached by the multi-head SpMM kernel in full-batch
GAT training, in %.

Least time: for each multi-head SpMM of an epoch (per layer, forward and
the cached transpose's), the larger of its operations over the bf16 peak
and its compulsory bytes over the HBM peak (``lib/gat_counts.
pass_compulsory``), times the epochs in the window. Time: the Pallas
kernel calls (``custom-call`` ops) in the window created under the
program's ``aggregate`` stage, by the op's creating stack in the program's
HLO: the kernels alone, not the layout work around them. A program
without the stage, or without a kernel there, reads nothing."""
from chipbench.lib import counts, trace


def read(view):
    spent = view.seconds(lambda op: op.opcode == "custom-call"
                         and trace.in_stack(op, "repro/obs/stages.py",
                                            "aggregate"))
    calls = view.work.get("spmm_calls_per_step")
    if spent <= 0 or not calls:
        return None
    least = sum(counts.least_time(f, b, view.peaks) for f, b in calls)
    return 100.0 * least * view.work["steps"] / spent
