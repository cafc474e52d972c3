"""iSpLib core: auto-tuned semiring sparse ops with cached backpropagation.

Public surface (the paper's user API, §3.5–3.6):

    from repro.core import matmul, spmm
    from repro.core import build_cached_graph, autotune, tuning_curve
    from repro.core import patch, unpatch, patched, patch_fn
"""
from repro.core.sparse import (COO, CSR, BSR, ELL, SELL, coo_from_edges,
                               csr_from_coo, bsr_from_coo, ell_from_coo,
                               sell_from_coo, sell_slice_degrees,
                               coo_transpose, gcn_normalize, row_degrees)
from repro.core.semiring import Semiring, get_semiring
from repro.core.autotune import (HardwareModel, KernelPlan, autotune,
                                 tuning_curve, suggest_embedding_size,
                                 probe_hardware, TuningDB)
from repro.core.cache import CachedGraph, build_cached_graph
from repro.core.spmm import spmm, matmul
from repro.core import baselines
from repro.core.patch import (patch, unpatch, patched, patch_fn, resolve,
                              is_patched, patch_version, _ensure_defaults)

_ensure_defaults()

__all__ = [
    "COO", "CSR", "BSR", "ELL", "SELL", "coo_from_edges", "csr_from_coo",
    "bsr_from_coo", "ell_from_coo", "sell_from_coo", "sell_slice_degrees",
    "coo_transpose", "gcn_normalize",
    "row_degrees", "Semiring", "get_semiring", "HardwareModel", "KernelPlan",
    "autotune", "tuning_curve", "suggest_embedding_size", "probe_hardware",
    "TuningDB", "CachedGraph", "build_cached_graph", "spmm", "matmul",
    "baselines", "patch", "unpatch", "patched",
    "patch_fn", "resolve", "is_patched", "patch_version",
]
