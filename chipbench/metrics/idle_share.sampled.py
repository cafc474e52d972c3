"""Device idle share of the sampled-training window, in %: one minus the union of
device-op intervals over the window (``lib/trace.py``), averaged over chips."""


def read(view):
    if view.window_s <= 0:
        return None
    return 100.0 * (1.0 - view.busy_s / view.window_s)
