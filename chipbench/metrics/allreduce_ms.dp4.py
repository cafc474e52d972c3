"""Device time per step of the gradient all-reduce in data-parallel sampled
training, in ms, per chip: every device op in the window created under
``repro/dist/collectives.py:sync_grads`` (the pmean of the gradients over the
data axis), by the op's creating stack in the program's HLO."""
from chipbench.lib import trace


def read(view):
    spent = view.seconds(lambda op: trace.in_stack(
        op, "repro/dist/collectives.py", "sync_grads"))
    if spent <= 0 or not view.work.get("steps"):
        return None
    return 1000.0 * spent / view.work["steps"]
