"""Host seconds of the program's packing of the graph for its kernel plan
(BSR, SELL or ELL, of A and its transpose) in the process, from the
program's always-live counter ``setup.pack_s`` (``repro.obs``). The rest
of ``bundle_s.fullbatch`` is normalisation, transposes and tuning. A
program without the counter reads nothing."""


def read(view):
    from repro import obs
    value = obs.metrics().snapshot().get("setup.pack_s")
    return value if value else None
