"""Distributed SpMM: 1-D row bands vs the 2-D vertex-cut grid.

The interesting number is communication: the 1-D path all-gathers the full
feature matrix per device per layer (O(N*K)), the 2-D path gathers one
column block and reduce-scatters one row block (O(N*K/sqrt(P))). The
trajectory records both the modeled per-device volumes and the measured
step times.

Runs in this process over ``jax.devices()`` — every attached device, one
process per chip. On a CPU host the device count is whatever the caller
forced with ``XLA_FLAGS=--xla_force_host_platform_device_count`` before
JAX started; such timings share one memory bus and say little about
ICI-attached chips. Every row names the platform and device count.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn


def run(n: int = 4096, k: int = 128, nnz: int = 200_000) -> list[dict]:
    from jax.sharding import AxisType

    from repro.core import coo_from_edges
    from repro.core.autotune import KernelPlan
    from repro.dist import (build_dist_graph, comm_volume, comm_volume_2d,
                            distributed_spmm, make_grid_mesh)
    from repro.dist.gnn2d import distributed_spmm_2d, partition_2d

    rng = np.random.default_rng(0)
    lin = rng.choice(n * n, size=nnz, replace=False)
    dst, src = lin // n, lin % n
    val = rng.standard_normal(nnz).astype(np.float32)
    a = coo_from_edges(src, dst, val, n, n)
    h = jnp.asarray(rng.standard_normal((n, k)), jnp.float32)

    grid = make_grid_mesh()
    pr, pc = grid.shape["row"], grid.shape["col"]
    band = jax.make_mesh((pr * pc,), ("data",), axis_types=(AxisType.Auto,))
    devices = len(jax.devices())
    platform = jax.devices()[0].platform
    rows = []

    g1 = build_dist_graph(a, pr * pc)
    with band:
        t = time_fn(jax.jit(lambda g, hh: distributed_spmm(g, hh, band)),
                    g1, h)
    rows.append(dict(op="spmm_1d_bands", s=t, **comm_volume(g1, k)))

    for plan, tag in ((None, "ell"),
                      (KernelPlan(kind="sell", sell_c=8), "sell_c8")):
        g2 = partition_2d(a, pr, pc, plan=plan)
        with grid:
            t = time_fn(jax.jit(
                lambda g, hh: distributed_spmm_2d(g, hh, grid)), g2, h)
        rows.append(dict(op=f"spmm_2d_{tag}", s=t, **comm_volume_2d(g2, k)))
        with grid:
            t = time_fn(jax.jit(lambda g, hh: distributed_spmm_2d(
                g, hh, grid, compress=True)), g2, h)
        rows.append(dict(op=f"spmm_2d_{tag}_int8", s=t,
                         **comm_volume_2d(g2, k)))

    for r in rows:
        r.update(platform=platform, devices=devices)
        emit(f"dist2d/{platform}{devices}dev/{r['op']}", r["s"],
             f"gather_rows={r['gather_rows']};elements={r['elements']}")
    return rows


if __name__ == "__main__":
    run()
