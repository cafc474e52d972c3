"""Device time per epoch of the model's aggregation in full-batch training,
in ms, per chip: every device op in the window created under the program's
``aggregate`` stage (``repro/obs/stages.py``: each SpMM of a layer, forward
and transpose, with the layout work around it), by the op's creating stack
in the program's HLO. A program without the stage reads nothing."""
from chipbench.lib import trace


def read(view):
    spent = view.seconds(lambda op: trace.in_stack(
        op, "repro/obs/stages.py", "aggregate"))
    if spent <= 0 or not view.work.get("steps"):
        return None
    return 1000.0 * spent / view.work["steps"]
