"""Host seconds of the program's ``build_bundle`` (normalisation, tuning and
packing of the graph), timed around the call in ``drivers/fullbatch.py``."""


def read(view):
    return view.work.get("bundle_s")
