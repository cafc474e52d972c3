"""Pass 2 — static audit of the repo's Pallas TPU kernels.

No TPU is needed: each registered kernel entry point (a ``*_pallas``
function, or a ``kernels/ops`` dispatcher with ``interpret=False``, which
takes its Pallas branch whatever ``on_tpu()`` says) is called eagerly on
tiny inputs with :func:`pl.pallas_call` intercepted.
The interceptor records the launch configuration — grid, BlockSpecs,
scalar-prefetch split, out_shape, scratch — **plus the concrete operand
arrays**, and returns zeros instead of executing, so the audit sees the
*real* scalar-prefetch routing tables (``idx``/``blk_row``/...) that the
BlockSpec index maps consume.

Checks per captured launch:

* **PAL001** — per-step VMEM working set: every blocked operand and
  output tile is double-buffered (compute on one copy while the next
  DMAs in), scratch is single-buffered, scalar-prefetch operands live in
  SMEM and don't count. The sum must fit the ~16 MiB/core budget.
* **PAL002 / PAL005** — index maps are evaluated numerically over the
  grid (exhaustively when small, boundary points otherwise). A block
  index outside ``[0, ceil(dim/block))`` is an OOB DMA: PAL005 when the
  value came from a prefetch table (sentinel-routing bug — e.g. dropping
  the appended zero row that makes ``idx == ncols`` legal), PAL002 when
  it is a pure function of the grid.
* **PAL003** — operand dims not divisible by their block shape (implicit
  Pallas padding; correct only if the kernel tolerates garbage lanes).
* **PAL004** — a ``(1, K>=128)`` output tile: each step drives one of
  the 8 f32 sublanes, wasting 7/8 of the VPU (the documented ELL
  penalty that motivated SELL-C-sigma).
* **PAL100** — info summary: grid, per-step VMEM bytes, points checked.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Any, Callable, Optional

import numpy as np

from repro.analysis.findings import Finding

__all__ = ["analyze_pallas", "audit_capture", "capture_pallas_calls",
           "PallasCapture", "KERNEL_TARGETS", "KernelTarget",
           "VMEM_BUDGET_BYTES"]

#: ~16 MiB of VMEM per TensorCore (see the Pallas TPU guide)
VMEM_BUDGET_BYTES = 16 * 2 ** 20

#: full-grid index-map evaluation cap; larger grids check boundary points
_MAX_GRID_POINTS = 65536


@dataclasses.dataclass
class PallasCapture:
    """One intercepted ``pl.pallas_call`` launch."""
    kernel_name: str
    grid: tuple
    num_scalar_prefetch: int
    in_specs: list            # BlockSpec per *blocked* operand
    out_specs: list           # BlockSpec per output
    out_shapes: list          # ShapeDtypeStruct per output
    scratch_shapes: list
    prefetch: list            # concrete SMEM operands (np arrays)
    operands: list            # concrete blocked operands (np arrays)


def _as_list(x) -> list:
    if x is None:
        return []
    return list(x) if isinstance(x, (tuple, list)) else [x]


@contextlib.contextmanager
def capture_pallas_calls():
    """Swap ``pallas_call`` for a recorder that returns zeros. Kernels
    resolve it at call time as a module attribute (``pl.pallas_call``),
    so patching the module is enough."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl_mod

    records: list[PallasCapture] = []
    orig = pl_mod.pallas_call

    def fake(kernel, *, grid_spec=None, grid=None, in_specs=None,
             out_specs=None, out_shape=None, **kw):
        del kw  # compiler_params / interpret — irrelevant statically
        if grid_spec is not None:
            g = grid_spec.grid
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
            ins = _as_list(grid_spec.in_specs)
            outs = _as_list(grid_spec.out_specs)
            scratch = _as_list(getattr(grid_spec, "scratch_shapes", None))
        else:
            g, nsp = grid, 0
            ins, outs, scratch = _as_list(in_specs), _as_list(out_specs), []
        g = (g,) if isinstance(g, int) else tuple(g)
        fn = getattr(kernel, "func", kernel)     # unwrap functools.partial
        name = getattr(fn, "__name__", str(kernel))
        shapes = _as_list(out_shape)

        def runner(*ops):
            records.append(PallasCapture(
                kernel_name=name, grid=g, num_scalar_prefetch=nsp,
                in_specs=ins, out_specs=outs, out_shapes=shapes,
                scratch_shapes=scratch,
                prefetch=[np.asarray(o) for o in ops[:nsp]],
                operands=[np.asarray(o) for o in ops[nsp:]]))
            zeros = [jnp.zeros(s.shape, s.dtype) for s in shapes]
            return zeros[0] if not isinstance(out_shape, (tuple, list)) \
                else type(out_shape)(zeros)

        return runner

    pl_mod.pallas_call = fake
    try:
        yield records
    finally:
        pl_mod.pallas_call = orig


# --------------------------------------------------------------------------
# per-capture checks
# --------------------------------------------------------------------------

def _block_shape(spec, operand_shape) -> tuple:
    bs = getattr(spec, "block_shape", None) if spec is not None else None
    if bs is None:
        return tuple(operand_shape)
    return tuple(operand_shape[i] if b is None else int(b)
                 for i, b in enumerate(bs))


def _grid_points(grid: tuple):
    total = 1
    for g in grid:
        total *= max(int(g), 1)
    if total <= _MAX_GRID_POINTS:
        return itertools.product(*(range(int(g)) for g in grid)), total
    axes = [sorted({0, int(g) // 2, int(g) - 1}) for g in grid]
    pts = list(itertools.product(*axes))
    return iter(pts), len(pts)


def _eval_map(spec, point, prefetch):
    fn = getattr(spec, "index_map", None)
    if fn is None:
        return None
    return tuple(int(v) for v in np.ravel(np.asarray(
        fn(*point, *prefetch))))


def _check_index_maps(cap: PallasCapture, file: str, obj: str,
                      findings: list) -> int:
    """Evaluate every (spec, operand) pair over the grid; returns the
    number of grid points visited."""
    pairs = (list(zip(cap.in_specs, [o.shape for o in cap.operands]))
             + list(zip(cap.out_specs, [s.shape for s in cap.out_shapes])))
    zero_tables = [np.zeros_like(p) for p in cap.prefetch]
    points, n_pts = _grid_points(cap.grid)
    bad: set[tuple] = set()
    for point in points:
        for si, (spec, oshape) in enumerate(pairs):
            bs = _block_shape(spec, oshape)
            idx = _eval_map(spec, point, cap.prefetch)
            if idx is None:
                continue
            for d, (bi, b, dim) in enumerate(zip(idx, bs, oshape)):
                nblocks = -(-int(dim) // int(b))       # ceil
                if 0 <= bi < nblocks:
                    continue
                key = (si, d)
                if key in bad:
                    continue
                bad.add(key)
                routed = False
                if cap.prefetch:
                    try:
                        routed = (_eval_map(spec, point, zero_tables)
                                  != idx)
                    except Exception:   # noqa: BLE001
                        routed = True
                which = ("output" if si >= len(cap.in_specs)
                         else f"operand {si}")
                findings.append(Finding(
                    code="PAL005" if routed else "PAL002",
                    file=file, obj=obj,
                    message=f"{cap.kernel_name}: {which} block index "
                            f"{bi} on dim {d} at grid point {point} is "
                            f"outside [0, {nblocks}) for operand dim "
                            f"{dim} / block {b}"
                            + (" (prefetch-routed gather — check the "
                               "sentinel row)" if routed else "")))
    return n_pts


def _check_divisibility(cap: PallasCapture, file: str, obj: str,
                        findings: list) -> None:
    pairs = (list(zip(cap.in_specs, [o.shape for o in cap.operands]))
             + list(zip(cap.out_specs, [s.shape for s in cap.out_shapes])))
    for si, (spec, oshape) in enumerate(pairs):
        bs = _block_shape(spec, oshape)
        for d, (b, dim) in enumerate(zip(bs, oshape)):
            if int(dim) % int(b):
                which = ("output" if si >= len(cap.in_specs)
                         else f"operand {si}")
                findings.append(Finding(
                    code="PAL003", file=file, obj=obj,
                    message=f"{cap.kernel_name}: {which} dim {d} "
                            f"({dim}) not divisible by block {b} — "
                            f"Pallas pads the tail block; the kernel "
                            f"must tolerate the padding lanes"))


def _in_vmem(x) -> bool:
    """Blocks and scratch default to VMEM; HBM (``pl.ANY``), SMEM and
    semaphore memory hold no VMEM working set."""
    space = getattr(x, "memory_space", None)
    return space is None or str(space) == "vmem"


def _vmem_bytes(cap: PallasCapture) -> int:
    total = 0
    for spec, op in zip(cap.in_specs, cap.operands):
        if _in_vmem(spec):
            bs = _block_shape(spec, op.shape)
            total += int(np.prod(bs)) * op.dtype.itemsize * 2  # dbl-buffered
    for spec, s in zip(cap.out_specs, cap.out_shapes):
        if _in_vmem(spec):
            bs = _block_shape(spec, s.shape)
            total += int(np.prod(bs)) * np.dtype(s.dtype).itemsize * 2
    for sc in cap.scratch_shapes:
        if _in_vmem(sc):
            total += int(np.prod(sc.shape)) * np.dtype(sc.dtype).itemsize
    return total


def _check_sublane(cap: PallasCapture, file: str, obj: str,
                   findings: list) -> None:
    for spec, s in zip(cap.out_specs, cap.out_shapes):
        bs = _block_shape(spec, s.shape)
        if (len(bs) == 2 and bs[0] == 1 and bs[1] >= 128
                and np.dtype(s.dtype).itemsize >= 4):
            findings.append(Finding(
                code="PAL004", file=file, obj=obj,
                message=f"{cap.kernel_name}: (1, {bs[1]}) output tile "
                        f"drives 1 of the 8 f32 sublanes per step — the "
                        f"ELL sublane penalty (SELL-C-sigma packs a "
                        f"(C, K) tile to fill them)"))


def audit_capture(cap: PallasCapture, *, file: str, obj: str,
                  vmem_budget: int = VMEM_BUDGET_BYTES) -> list[Finding]:
    """All static checks over one captured launch, plus the PAL100
    summary."""
    findings: list[Finding] = []
    vmem = _vmem_bytes(cap)
    if vmem > vmem_budget:
        findings.append(Finding(
            code="PAL001", file=file, obj=obj,
            message=f"{cap.kernel_name}: per-step VMEM working set "
                    f"{vmem} B (blocks x dtype x double buffering + "
                    f"scratch) exceeds the {vmem_budget} B budget"))
    n_pts = _check_index_maps(cap, file, obj, findings)
    _check_divisibility(cap, file, obj, findings)
    _check_sublane(cap, file, obj, findings)
    findings.append(Finding(
        code="PAL100", file=file, obj=obj,
        message=f"{cap.kernel_name}: grid={cap.grid} "
                f"vmem_per_step={vmem}B ({vmem / vmem_budget:.1%} of "
                f"budget), {n_pts} grid points checked, "
                f"{cap.num_scalar_prefetch} prefetch operands",
        detail={"grid": list(cap.grid), "vmem_bytes": vmem,
                "grid_points_checked": n_pts}))
    return findings


# --------------------------------------------------------------------------
# registered kernel targets — tiny representative launches
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelTarget:
    name: str
    file: str
    run: Callable       # () -> None; calls the kernel under capture


def _tiny_coo(n: int = 32, deg: int = 3, seed: int = 0):
    from repro.core import sparse as sp
    rng = np.random.default_rng(seed)
    dst = np.repeat(np.arange(n), deg)
    src = rng.integers(0, n, size=n * deg)
    return sp.coo_from_edges(src, dst, np.ones(n * deg, np.float32), n, n)


def _run_ell():
    import jax.numpy as jnp
    from repro.core import sparse as sp
    from repro.kernels import ops
    a = sp.ell_from_coo(_tiny_coo())
    ops.ell_spmm(a, jnp.ones((a.ncols, 4), jnp.float32), interpret=False)


def _run_sell():
    import jax.numpy as jnp
    from repro.core import sparse as sp
    from repro.kernels import ops
    a = sp.sell_from_coo(_tiny_coo(), c=8)
    ops.sell_spmm(a, jnp.ones((a.ncols, 4), jnp.float32), interpret=False)


def _run_bsr():
    import jax.numpy as jnp
    from repro.core import sparse as sp
    from repro.kernels.bsr_spmm import bsr_spmm_pallas
    a = sp.bsr_from_coo(_tiny_coo(), br=8, bc=8)
    bsr_spmm_pallas(a, jnp.ones((a.ncols, 4), jnp.float32))


def _run_gather_heads():
    import jax.numpy as jnp
    from repro.core import sparse as sp
    from repro.kernels import ops
    a = sp.sell_from_coo(_tiny_coo(), c=8)
    ops.gather_spmm_heads(a, jnp.ones((2, a.idx.size), jnp.float32),
                          jnp.ones((a.ncols, 8), jnp.float32),
                          interpret=False)


def _run_gather_sddmm():
    import jax.numpy as jnp
    from repro.core import sparse as sp
    from repro.kernels import ops
    a = sp.sell_from_coo(_tiny_coo(), c=8)
    x = jnp.ones((a.ncols, 8), jnp.float32)
    ops.gather_sddmm(a, x, x, heads=2, interpret=False)


def _run_flash():
    import jax.numpy as jnp
    from repro.kernels.flash_attention import flash_attention_pallas
    q = jnp.ones((1, 2, 8, 128), jnp.float32)
    kv = jnp.ones((1, 1, 8, 128), jnp.float32)
    flash_attention_pallas(q, kv, kv)


def _run_ragged():
    import jax.numpy as jnp
    from repro.kernels.ragged_gemm import ragged_gemm_pallas
    x = jnp.ones((128, 8), jnp.float32)
    w = jnp.ones((2, 8, 256), jnp.float32)
    ragged_gemm_pallas(x, w, jnp.zeros((1,), jnp.int32))


def _run_segment_sample():
    import jax.numpy as jnp
    from repro.kernels.sample import _segment_sample_pallas
    deg = jnp.array([3, 0, 2, 5, 1], jnp.int32)
    gid = jnp.arange(5, dtype=jnp.int32)
    _segment_sample_pallas(deg, gid, jnp.int32(0), width=2, fanout=2,
                           seed=0, hop=0, replace=False, interpret=False)


def _run_expand_indptr():
    import jax.numpy as jnp
    from repro.kernels.sample import _expand_indptr_pallas
    start = jnp.array([0, 3, 3, 5, 10], jnp.int32)
    ranks = jnp.zeros((5, 2), jnp.int32)
    vmask = jnp.ones((5, 2), bool)
    _expand_indptr_pallas(start, ranks, vmask, sentinel=12,
                          interpret=False)


def _run_flat_gather():
    import jax.numpy as jnp
    from repro.kernels.sample import _flat_gather_pallas
    arr = jnp.arange(300, dtype=jnp.int32)
    pos = jnp.array([[0, 5], [130, 299], [17, 250], [1, 2]], jnp.int32)
    _flat_gather_pallas(arr, pos, interpret=False)


KERNEL_TARGETS: tuple[KernelTarget, ...] = (
    KernelTarget("ell_spmm", "src/repro/kernels/gather_spmm.py", _run_ell),
    KernelTarget("sell_spmm", "src/repro/kernels/gather_spmm.py", _run_sell),
    KernelTarget("bsr_spmm_pallas", "src/repro/kernels/bsr_spmm.py",
                 _run_bsr),
    KernelTarget("gather_spmm_heads", "src/repro/kernels/gather_spmm.py",
                 _run_gather_heads),
    KernelTarget("gather_sddmm", "src/repro/kernels/gather_spmm.py",
                 _run_gather_sddmm),
    KernelTarget("flash_attention_pallas",
                 "src/repro/kernels/flash_attention.py", _run_flash),
    KernelTarget("ragged_gemm_pallas", "src/repro/kernels/ragged_gemm.py",
                 _run_ragged),
    KernelTarget("segment_sample", "src/repro/kernels/sample.py",
                 _run_segment_sample),
    KernelTarget("expand_indptr", "src/repro/kernels/sample.py",
                 _run_expand_indptr),
    KernelTarget("flat_gather", "src/repro/kernels/sample.py",
                 _run_flat_gather),
)


def analyze_pallas(targets: tuple[KernelTarget, ...] = KERNEL_TARGETS
                   ) -> list[Finding]:
    findings: list[Finding] = []
    for t in targets:
        try:
            with capture_pallas_calls() as records:
                t.run()
        except Exception as e:      # noqa: BLE001
            findings.append(Finding(
                code="PAL002", file=t.file, obj=t.name,
                message=f"audit launch failed before capture: "
                        f"{type(e).__name__}: {e}"))
            continue
        for cap in records:
            findings.extend(audit_capture(cap, file=t.file, obj=t.name))
    return findings
