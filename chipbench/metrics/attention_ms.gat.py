"""Device time per epoch of the GAT layers' attention, in ms, per chip:
every device op in the window created under the program's ``attention``
stage (``repro/obs/stages.py``: node scores, slot logits, LeakyReLU, the
edge softmax, their backward and the gather-SDDMM), by the op's creating
stack in the program's HLO. A program without the stage reads nothing."""
from chipbench.lib import trace


def read(view):
    spent = view.seconds(lambda op: trace.in_stack(
        op, "repro/obs/stages.py", "attention"))
    if spent <= 0 or not view.work.get("steps"):
        return None
    return 1000.0 * spent / view.work["steps"]
