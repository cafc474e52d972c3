"""Host seconds the process spent tracing, lowering and compiling (or
loading from the persistent cache) the full-batch step program
(``work["step_module"]``), from the program's compile log
(``repro.obs.compiles``, fed by ``jax.monitoring``): every call of
``train_gnn`` builds its step anew. A program without the log reads
nothing."""


def read(view):
    try:
        from repro.obs import compiles
    except ImportError:
        return None
    phases = compiles.program(view.work["step_module"])
    if not phases:
        return None
    return sum(seconds for _, seconds in phases.values())
