"""Share of its roofline reached by the library's SpMM in full-batch
training, in %.

Least time: for each aggregation of an epoch (``Â`` at widths hidden and
classes, ``Âᵀ`` at both), the larger of its operations over the bf16 peak and
its compulsory bytes over the HBM peak (``lib/counts.spmm_compulsory``: each
stored entry of ``Â`` once, the dense operand once, the output once), times
the epochs in the window. Time: every device op in the window created under
``repro/core/spmm.py:spmm`` (the Pallas kernel, forward and transpose, and the
layout work around it), by the op's creating stack in the program's HLO."""
from chipbench.lib import counts, trace


def read(view):
    spent = view.seconds(lambda op: trace.in_stack(op, "repro/core/spmm.py",
                                                   "spmm"))
    if spent <= 0:
        return None
    least = sum(counts.least_time(f, b, view.peaks)
                for f, b in view.work["spmm_calls_per_step"])
    return 100.0 * least * view.work["steps"] / spent
