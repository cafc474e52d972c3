"""Op-level kernel benchmarks: SpMM (trusted / BSR / ELL / SELL-C-σ).

Wall-clock is CPU (XLA paths — the same algorithmic shapes the Pallas
kernels implement); the analytic v5e roofline fraction per op comes from the
autotuner's cost model and is reported alongside. The SELL rows sweep the
slice height C so the ELL-vs-SELL packing win (per-slice padding + full
sublane tiles) is visible directly in the trajectory JSON.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core import (bsr_from_coo, build_cached_graph, ell_from_coo,
                        get_semiring, sell_from_coo)
from repro.core.autotune import (HardwareModel, KernelPlan, autotune,
                                 estimate_plan_time, graph_stats)
from repro.data import make_dataset
from repro.kernels import ops as kops
from repro.kernels.ref import spmm_coo_ref, spmm_ell_ref


def run(dataset: str = "reddit", scale=1 / 64, k: int = 128) -> list[dict]:
    ds = make_dataset(dataset, scale=scale)
    a = ds.coo
    hw = HardwareModel()
    stats = graph_stats(a)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((a.ncols, k)).astype(np.float32))
    rows = []

    sr = get_semiring("sum")
    t = time_fn(jax.jit(lambda hh: spmm_coo_ref(a, hh, sr)), h)
    est = estimate_plan_time(stats, k, KernelPlan.trusted(), hw)
    rows.append(dict(op="spmm_trusted", s=t, v5e_est_s=est))

    bsr = bsr_from_coo(a, br=128, bc=128)
    t = time_fn(jax.jit(lambda hh: kops.bsr_spmm(bsr, hh)), h)
    est = estimate_plan_time(stats, k, KernelPlan(kind="bsr"), hw)
    rows.append(dict(op="spmm_bsr", s=t, v5e_est_s=est))

    # the (1, K)-tile ELL path, p99-capped as before (full max_deg on a
    # power-law graph would not fit a laptop's RAM — which is the point)
    ell = ell_from_coo(a, max_deg=int(stats.p99_deg))
    t = time_fn(jax.jit(lambda hh: spmm_ell_ref(ell, hh, sr)), h)
    est = estimate_plan_time(stats, k, KernelPlan(kind="ell"), hw)
    rows.append(dict(op="spmm_ell", s=t, v5e_est_s=est))

    # SELL-C-σ: exact (no cap needed — per-slice padding absorbs the skew)
    for c in (8, 16, 32):
        sell = sell_from_coo(a, c=c, sigma=0)
        t = time_fn(jax.jit(lambda hh: kops.sell_spmm(sell, hh)), h)
        est = estimate_plan_time(
            stats, k, KernelPlan(kind="sell", sell_c=c, sell_sigma=0), hw)
        rows.append(dict(op=f"spmm_sell_c{c}", s=t, v5e_est_s=est,
                         pack_eff=round(sell.packing_efficiency, 3)))

    # the autotuned plan's own pick, dispatched through the CachedGraph
    plan = autotune(a, k)
    g_tuned = build_cached_graph(a, k_hint=k, plan=plan)
    from repro.core import spmm as spmm_fn
    t = time_fn(jax.jit(lambda hh: spmm_fn(g_tuned, hh)), h)
    rows.append(dict(op="spmm_autotuned", s=t, v5e_est_s=None,
                     plan=plan.kind))

    for r in rows:
        extra = (f"v5e_est_us={r['v5e_est_s'] * 1e6:.1f}"
                 if r["v5e_est_s"] else "")
        if "plan" in r:
            extra += f";plan={r['plan']}"
        emit(f"kernel/{dataset}/{r['op']}", r["s"], extra)
    return rows


if __name__ == "__main__":
    run()
