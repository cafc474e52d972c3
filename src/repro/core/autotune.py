"""Auto-tuning mechanism (paper §3.2) adapted from CPU SIMD to TPU.

iSpLib probes the CPU for SIMD VLEN and generates unrolled/register-blocked
kernels for embedding sizes that are VLEN multiples, with a generic "trusted"
kernel for everything else; a tuning pass sweeps K and reports the
generated-vs-trusted speedup curve (Fig. 2).

TPU translation implemented here:

* the *hardware probe* returns a :class:`HardwareModel` — MXU dim, VMEM
  and SMEM capacity, HBM/ICI bandwidths, peak MXU/VPU FLOP/s (defaults =
  TPU v5e, the target platform; on a TPU the probe looks the device's
  reported kind up in :data:`TPU_PEAKS` and raises for an unknown one);
* the *generated kernels* are the BSR (MXU matmul), ELL and SELL-C-σ
  (row-gather, ``kernels/gather_spmm.py``) Pallas kernels — a *fit rule*
  (:func:`plan_fit_error`) keeps any plan the chip cannot hold (packed
  operands past HBM, prefetched tables past SMEM) out of the sweep; *trusted* is the
  XLA gather+segment-sum path that handles any (K, semiring, sparsity)
  point;
* "K a multiple of VLEN" becomes "K a multiple of 128 lanes";
* "register blocking" becomes picking the (Br, Bc, Fk) BlockSpec tile so the
  working set fits VMEM and the MXU dims are aligned — and, for SELL, the
  slice height C (full-sublane (C, K) accumulator tiles) plus the sort
  window σ;
* the *tuning pass* sweeps candidate plans through an analytic roofline cost
  model (and, when ``measure=True``, wall-clock on whatever backend is
  attached — the honest CPU proxy used for the Fig. 2 reproduction; the
  measured pass times every eligible family: trusted, BSR, ELL and SELL);
* one-time-tuning amortization (§3.2's "tune once per platform") is the
  :class:`TuningDB` — ``build_cached_graph(db=...)`` consults it before
  sweeping and persists measured decisions across runs.

Module map
----------
``HardwareModel``/``probe_hardware``  roofline constants per chip kind
``plan_fit_error``                    what the chip cannot hold
``GraphStats``/``graph_stats``        host-side sparsity fingerprint
                                      (incl. per-(C, σ) SELL packed sizes)
``KernelPlan``                        the tuner's hashable decision
``estimate_plan_time``                analytic roofline cost per plan
``autotune``/``_measure_override``    analytic sweep + measured override
``tuning_curve``                      Fig. 2 reproduction sweep over K
``TuningDB``                          persisted decisions (JSON, keyed by
                                      structural graph fingerprint + K)

The output is a :class:`KernelPlan` — a hashable static decision that the
``CachedGraph`` stores (metadata, not traced) so jitted training steps
specialize on it.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
import warnings
from typing import Any, Callable, Sequence

import jax
import numpy as np

__all__ = [
    "HardwareModel",
    "TPU_PEAKS",
    "KernelPlan",
    "GraphStats",
    "probe_hardware",
    "plan_fit_error",
    "graph_stats",
    "estimate_plan_time",
    "autotune",
    "tuning_curve",
    "TuningDB",
    "sell_sigma_candidates",
    "sell_candidates_from_degrees",
]


# --------------------------------------------------------------------------
# Hardware model (the probe)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """Roofline constants for the target chip. Defaults: TPU v5e."""

    name: str = "tpu-v5e"
    mxu_dim: int = 128                 # systolic array edge
    lane: int = 128                    # vreg lane count (last-dim alignment)
    sublane: int = 8                   # second-minor alignment (fp32)
    vmem_bytes: int = 64 * 1024 * 1024
    # scalar memory the compiler gives a kernel's scalar-prefetched tables
    # (its allocation error on v5e names 1,048,576 bytes)
    smem_bytes: int = 1024 * 1024
    hbm_bytes: int = 16 * 1000 ** 3
    peak_flops: float = 197e12         # bf16 MXU
    vpu_flops: float = 197e12 / 16     # non-matmul (VPU) throughput model
    hbm_bw: float = 819e9              # bytes/s
    ici_bw: float = 50e9               # bytes/s per link

    def mxu_time(self, flops: float) -> float:
        return flops / self.peak_flops

    def vpu_time(self, flops: float) -> float:
        return flops / self.vpu_flops

    def mem_time(self, nbytes: float) -> float:
        return nbytes / self.hbm_bw


# Published per-chip peaks keyed by ``jax.Device.device_kind`` — bf16 MXU
# FLOP/s, HBM capacity and bandwidth. Source: Google Cloud TPU
# documentation, system architecture pages "TPU v4", "TPU v5e", "TPU v5p"
# and "TPU v6e". A TPU whose kind is missing here is an error, never a
# silent default.
TPU_PEAKS: dict[str, dict] = {
    "TPU v4": dict(name="tpu-v4", peak_flops=275e12, hbm_bw=1200e9,
                   hbm_bytes=32 << 30, vmem_bytes=128 << 20),
    "TPU v5 lite": dict(name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                        hbm_bytes=16 * 1000 ** 3),
    "TPU v5": dict(name="tpu-v5p", peak_flops=459e12, hbm_bw=2765e9,
                   hbm_bytes=95 * 1000 ** 3, vmem_bytes=128 << 20),
    "TPU v6 lite": dict(name="tpu-v6e", peak_flops=918e12, hbm_bw=1640e9,
                        hbm_bytes=32 * 1000 ** 3, vmem_bytes=128 << 20),
}


def probe_hardware() -> HardwareModel:
    """Probe the attached backend. On TPU, the constants come from
    :data:`TPU_PEAKS` by the device's reported kind, and an unknown kind
    raises. Everywhere else, return the v5e *target* model: off the chip
    the model is used analytically, to plan for the chip."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return HardwareModel()
    kind = dev.device_kind
    if kind not in TPU_PEAKS:
        raise ValueError(
            f"no published peaks for TPU device kind {kind!r}; add them to "
            f"repro.core.autotune.TPU_PEAKS (known: {sorted(TPU_PEAKS)})")
    return HardwareModel(**TPU_PEAKS[kind])


# --------------------------------------------------------------------------
# Graph statistics (host-side, cheap, computed once)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GraphStats:
    nrows: int
    ncols: int
    nse: int
    avg_deg: float
    max_deg: int
    p99_deg: int
    # per candidate (br, bc): number of nonempty tiles
    tile_counts: tuple  # ((br, bc, n_tiles), ...)
    # per candidate (c, sigma): SELL packed step count Σ_s max_deg_s
    sell_counts: tuple = ()  # ((c, sigma, n_steps), ...)

    def n_tiles(self, br: int, bc: int) -> int:
        for b_r, b_c, n in self.tile_counts:
            if (b_r, b_c) == (br, bc):
                return n
        raise KeyError((br, bc))

    def sell_steps(self, c: int, sigma: int) -> int:
        for cc, ss, n in self.sell_counts:
            if (cc, ss) == (c, sigma):
                return n
        raise KeyError((c, sigma))


_DEFAULT_TILES: tuple = ((128, 128), (256, 128), (128, 256), (64, 128), (32, 128))
# SELL slice heights swept by the tuner (sublane multiples). Sort windows
# (σ) are derived per graph from the degree histogram — see
# :func:`sell_sigma_candidates`; ``_SELL_SIGMA_FALLBACK`` serves degenerate
# (empty / degree-free) graphs where no histogram exists.
_SELL_C_VALUES: tuple = (8, 16, 32)
_SELL_SIGMA_FALLBACK: tuple = (0, 256)
_SELL_SIGMA_MAX: int = 3     # hard cap on σ candidates per graph — the
                             # measured sweep times |C| x |σ| variants


def sell_sigma_candidates(degrees: np.ndarray,
                          fallback: Sequence[int] = _SELL_SIGMA_FALLBACK
                          ) -> tuple:
    """Derive SELL sort-window (σ) candidates from the degree histogram.

    The knee of the Lorenz curve — the row count at which the sorted-degree
    cumulative mass is furthest above the uniform diagonal — is how many
    rows carry the graph's "excess" degree. A sort window just covering
    that knee groups the heavy rows without paying a global permutation;
    the candidate set is {0 (global sort), knee window, 4x knee window}
    clipped to the row count and capped at ``_SELL_SIGMA_MAX`` entries.
    Degenerate histograms are cut short instead of inflating the measured
    sweep: no rows / no edges gets the static fallback, and a
    constant-degree graph gets ``(0,)`` alone — every sort window is a
    no-op permutation there, so the Lorenz knee (which degenerates to row
    1) would only emit duplicate-effect windows.
    """
    deg = np.asarray(degrees, np.int64)
    n = int(deg.shape[0])
    if n == 0 or deg.sum() == 0:
        return tuple(fallback)
    d = np.sort(deg)[::-1]
    if d[0] == d[-1]:                                # constant degrees
        return (0,)
    lorenz = np.cumsum(d) / d.sum()                  # mass of top-i rows
    frac = np.arange(1, n + 1) / n                   # uniform diagonal
    knee = int(np.argmax(lorenz - frac)) + 1         # rows holding the excess
    window = 1 << int(np.ceil(np.log2(max(knee, 8))))
    cands = {0}
    for w in (window, 4 * window):
        if w < n:                                    # >= n degenerates to 0
            cands.add(w)
    return tuple(sorted(cands))[:_SELL_SIGMA_MAX]


def sell_candidates_from_degrees(degrees: np.ndarray,
                                 c_values: Sequence[int] = _SELL_C_VALUES
                                 ) -> tuple:
    """(C, σ) sweep set: slice heights x histogram-derived sort windows."""
    return tuple((c, s) for c in c_values
                 for s in sell_sigma_candidates(degrees))


def graph_stats(a, tile_candidates: Sequence[tuple] = _DEFAULT_TILES,
                sell_candidates: Sequence[tuple] | None = None
                ) -> GraphStats:
    """``a`` is a COO (repro.core.sparse). Host-side numpy pass.
    ``sell_candidates=None`` derives the (C, σ) sweep from the degree
    histogram (:func:`sell_candidates_from_degrees`)."""
    from repro.core.sparse import sell_slice_degrees
    row = np.asarray(a.row)[: a.nse].astype(np.int64)
    col = np.asarray(a.col)[: a.nse].astype(np.int64)
    deg = np.bincount(row, minlength=a.nrows)
    if sell_candidates is None:
        sell_candidates = sell_candidates_from_degrees(deg)
    counts = []
    for br, bc in tile_candidates:
        nbc = -(-a.ncols // bc)
        key = (row // br) * nbc + (col // bc)
        counts.append((br, bc, int(np.unique(key).size)))
    sells = []
    for c, sigma in sell_candidates:
        slice_deg, _ = sell_slice_degrees(deg, c, sigma)
        sells.append((c, sigma, int(slice_deg.sum())))
    return GraphStats(
        nrows=a.nrows, ncols=a.ncols, nse=a.nse,
        avg_deg=float(deg.mean()) if a.nrows else 0.0,
        max_deg=int(deg.max()) if a.nrows else 0,
        p99_deg=int(np.percentile(deg, 99)) if a.nrows else 0,
        tile_counts=tuple(counts),
        sell_counts=tuple(sells),
    )


# --------------------------------------------------------------------------
# Kernel plan — the tuner's (static, hashable) decision
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Which kernel variant serves a (graph, K) point, plus its tile shape.

    kind:
      'bsr'      generated kernel, MXU block-sparse matmul   (sum/mean only)
      'ell'      generated kernel, VPU row-gather            (sum/mean)
      'sell'     generated kernel, SELL-C-σ sliced gather    (sum/mean)
      'trusted'  XLA gather + segment-reduce                 (any anything)
    """

    kind: str = "trusted"
    br: int = 128
    bc: int = 128
    fk: int = 256           # K tile of the Pallas grid
    k_hint: int = 128       # embedding width the plan was tuned for
    sell_c: int = 8         # SELL slice height (sublane tile)
    sell_sigma: int = 0     # SELL sort window (0 = global sort)
    est_generated_s: float = float("inf")
    est_trusted_s: float = float("inf")

    def __post_init__(self):
        assert self.kind in ("bsr", "ell", "sell", "trusted"), self.kind

    @property
    def wants_bsr(self) -> bool:
        return self.kind == "bsr"

    @property
    def wants_ell(self) -> bool:
        return self.kind == "ell"

    @property
    def wants_sell(self) -> bool:
        return self.kind == "sell"

    @property
    def predicted_speedup(self) -> float:
        if self.kind == "trusted" or self.est_generated_s == 0:
            return 1.0
        return self.est_trusted_s / self.est_generated_s

    @classmethod
    def trusted(cls, k_hint: int = 128) -> "KernelPlan":
        return cls(kind="trusted", k_hint=k_hint)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "KernelPlan":
        return cls(**d)


# --------------------------------------------------------------------------
# Analytic cost model (napkin math the tuner automates)
# --------------------------------------------------------------------------

def _bytes_of(dtype) -> int:
    return np.dtype(dtype).itemsize


def estimate_plan_time(stats: GraphStats, k: int, plan: KernelPlan,
                       hw: HardwareModel, dtype=np.float32) -> float:
    """Seconds for one SpMM under the roofline model: max(compute, memory)."""
    e = _bytes_of(dtype)
    if plan.kind == "bsr":
        nt = stats.n_tiles(plan.br, plan.bc)
        flops = 2.0 * nt * plan.br * plan.bc * k
        # A tiles stream once; H tiles are re-fetched per owning tile (upper
        # bound: no reuse across tiles); C revisits stay in VMEM.
        nbytes = nt * (plan.br * plan.bc * e + plan.bc * k * e) \
            + stats.nrows * k * e
        return max(hw.mxu_time(flops), hw.mem_time(nbytes))
    if plan.kind == "ell":
        md = max(stats.p99_deg, 1)
        flops = 2.0 * stats.nrows * md * k
        nbytes = stats.nrows * md * (4 + k * e) + stats.nrows * k * e
        # (1, K) output tiles drive one of `sublane` VPU sublanes per step —
        # the structural inefficiency SELL-C-σ exists to fix.
        return max(hw.vpu_time(flops * hw.sublane), hw.mem_time(nbytes))
    if plan.kind == "sell":
        steps = stats.sell_steps(plan.sell_c, plan.sell_sigma)
        slots = steps * plan.sell_c        # stored (idx, val) pairs
        flops = 2.0 * slots * k
        # full (C, K) accumulator tiles -> all sublanes busy; packed layout
        # streams exactly `slots` neighbor rows + the output once.
        nbytes = slots * (4 + k * e) + stats.nrows * k * e
        return max(hw.vpu_time(flops), hw.mem_time(nbytes))
    # trusted: per-edge gather + scatter-add, VPU-bound, poor locality.
    flops = 2.0 * stats.nse * k
    nbytes = stats.nse * (8 + 2 * k * e) + stats.nrows * k * e
    return max(hw.vpu_time(flops), hw.mem_time(nbytes))


def _vmem_ok(br: int, bc: int, fk: int, hw: HardwareModel,
             dtype=np.float32) -> bool:
    """A-tile + H-tile + C-accumulator (+double buffering) must fit VMEM."""
    e = _bytes_of(dtype)
    need = 2 * (br * bc * e + bc * fk * e) + br * fk * 4  # acc fp32
    return need <= hw.vmem_bytes * 0.8


def plan_fit_error(stats: GraphStats, plan: KernelPlan, hw: HardwareModel,
                   dtype=np.float32) -> str | None:
    """Why ``plan`` cannot run on ``hw`` at this graph's size, or None.

    Two budgets, both for A and its cached transpose together:

    * the packed operands must fit half of HBM (the rest holds features,
      activations and optimizer state) — BSR at full-size reddit needs
      ~1 M dense 128x128 tiles, tens of GB;
    * the kernel's scalar-prefetched table must fit half of SMEM: BSR
      prefetches two words per block, ELL and SELL one word per output
      tile of 8 / C rows.

    A plan that fails either is left out of the candidate set with this
    reason instead of failing to compile or run on the chip."""
    e = _bytes_of(dtype)
    hbm, smem = hw.hbm_bytes // 2, hw.smem_bytes // 2
    if plan.kind == "bsr":
        nb = stats.n_tiles(plan.br, plan.bc) + -(-stats.nrows // plan.br)
        packed = 2 * nb * (plan.br * plan.bc * e + 8)
        table = 2 * nb * 4
    elif plan.kind == "ell":
        packed = 2 * stats.nrows * max(stats.max_deg, 1) * (4 + e)
        table = (-(-stats.nrows // 8) + 1) * 4
    elif plan.kind == "sell":
        steps = stats.sell_steps(plan.sell_c, plan.sell_sigma)
        packed = 2 * steps * (plan.sell_c * (4 + e) + 4)
        table = (-(-stats.nrows // plan.sell_c) + 1) * 4
    else:
        return None
    label = _plan_label(plan)
    if packed > hbm:
        return (f"{label}: packed A and A^T need {packed / 1e9:.1f} GB, over "
                f"half of the {hw.hbm_bytes / 1e9:.0f} GB HBM of {hw.name}")
    if table > smem:
        return (f"{label}: its scalar-prefetched table needs {table} bytes, "
                f"over half of the {hw.smem_bytes} bytes of SMEM")
    return None


# --------------------------------------------------------------------------
# The tuner
# --------------------------------------------------------------------------

def autotune(a, k_hint: int = 128, *, hw: HardwareModel | None = None,
             measure: bool = False, semiring_reduce: str = "sum",
             tile_candidates: Sequence[tuple] = _DEFAULT_TILES,
             sell_candidates: Sequence[tuple] | None = None,
             stats: GraphStats | None = None) -> KernelPlan:
    """Pick the kernel variant + tile shape for (graph ``a``, width ``k_hint``).

    Mirrors the paper's eligibility rules:
      * generated (MXU) kernels serve only lane-aligned K and the sum/mean
        semiring (§3.4: "only the sum reduction operation has the generated
        kernel support");
      * any other point falls back to the trusted kernel, "still efficient
        with balanced multithreading" (= XLA's fused gather/segment path).

    ``measure=True`` additionally times jitted candidates on the attached
    backend and overrides the analytic pick (used by the Fig. 2 bench); the
    measured pass covers every eligible family — trusted, BSR, ELL, SELL —
    and times the ``semiring_reduce`` actually requested (mean pays its
    inverse-degree post-scale, max/min their segment reduce), so plans for
    different semirings carry their own measured costs.

    ``sell_candidates=None`` (default) derives the (C, σ) sweep from the
    graph's degree histogram — the knee of the Lorenz curve sets the sort
    windows (:func:`sell_sigma_candidates`).
    """
    hw = hw or probe_hardware()
    stats = stats or graph_stats(a, tile_candidates, sell_candidates)

    trusted = KernelPlan.trusted(k_hint)
    t_trusted = estimate_plan_time(stats, k_hint, trusted, hw)
    evaluated: list = [("trusted", t_trusted)]

    lane_aligned = k_hint % hw.lane == 0
    mxu_semiring = semiring_reduce in ("sum", "mean")
    if not (lane_aligned and mxu_semiring):
        plan = dataclasses.replace(trusted, est_trusted_s=t_trusted,
                                   est_generated_s=float("inf"))
        _log_sweep(stats, k_hint, semiring_reduce, evaluated, plan,
                   gated="lane" if not lane_aligned else "semiring")
        if measure:     # record a measured trusted row for this semiring
            plan = _measure_override(a, k_hint, plan, stats, hw=hw,
                                     semiring=semiring_reduce)
        return plan

    best: KernelPlan = dataclasses.replace(
        trusted, est_trusted_s=t_trusted, est_generated_s=float("inf"))
    best_t = t_trusted
    unfit: list = []        # (label, reason) of candidates the chip can't run
    for cand in _generated_candidates(stats, k_hint, hw, tile_candidates):
        why = plan_fit_error(stats, cand, hw)
        if why is not None:
            unfit.append((_plan_label(cand), why))
            continue
        t = estimate_plan_time(stats, k_hint, cand, hw)
        evaluated.append((_plan_label(cand), t))
        if t < best_t:
            best_t = t
            best = dataclasses.replace(cand, est_generated_s=t,
                                       est_trusted_s=t_trusted)

    _log_sweep(stats, k_hint, semiring_reduce, evaluated, best, unfit=unfit)
    if measure:
        best = _measure_override(a, k_hint, best, stats, hw=hw,
                                 semiring=semiring_reduce)
    return best


def _generated_candidates(stats: GraphStats, k_hint: int, hw: HardwareModel,
                          tile_candidates: Sequence[tuple]) -> list:
    """Every generated-kernel plan the sweep considers, before the fit
    rule (:func:`plan_fit_error`) removes what the chip cannot hold."""
    cands = []
    fk = min(256, max(128, ((k_hint + 127) // 128) * 128))
    for br, bc in tile_candidates:
        if _vmem_ok(br, bc, fk, hw):
            cands.append(KernelPlan(kind="bsr", br=br, bc=bc, fk=fk,
                                    k_hint=k_hint))
    # ELL candidate: only when padding is bounded (near-regular degree).
    if stats.max_deg <= max(4 * stats.avg_deg, 8):
        cands.append(KernelPlan(kind="ell", k_hint=k_hint))
    # SELL-C-σ candidates: per-slice padding makes these eligible for ANY
    # degree distribution — the sort absorbs the skew the ELL rule rejects.
    # The sweep set always comes from ``stats`` so cost model and packing
    # agree on the step counts (histogram-derived unless the caller pinned
    # candidates explicitly).
    for c, sigma, _ in stats.sell_counts:
        cands.append(KernelPlan(kind="sell", sell_c=c, sell_sigma=sigma,
                                k_hint=k_hint))
    return cands


def _plan_label(plan: KernelPlan) -> str:
    """Short human-readable plan tag used in decision logs and summaries."""
    if plan.kind == "bsr":
        return f"bsr{plan.br}x{plan.bc}"
    if plan.kind == "sell":
        return f"sellc{plan.sell_c}s{plan.sell_sigma}"
    return plan.kind


def _log_sweep(stats: GraphStats, k: int, semiring: str, evaluated: list,
               winner: KernelPlan, *, gated: str | None = None,
               unfit: list = ()) -> None:
    """Emit one ``tuning.sweep`` decision event (analytic pass) — every
    candidate with its estimated seconds, the candidates left out because
    the chip cannot hold them (with the reason), plus the pick. No-op
    unless the obs tracer is enabled; always bumps the sweep counter."""
    from repro import obs
    obs.metrics().counter("tuning.sweeps").inc()
    if not obs.enabled():
        return
    attrs = dict(
        graph=f"{stats.nrows}x{stats.ncols}nse{stats.nse}", k=k,
        semiring=semiring, winner=_plan_label(winner),
        candidates=[[name, float(t)] for name, t in evaluated])
    if gated:
        attrs["gated"] = gated
    if unfit:
        attrs["unfit"] = [list(u) for u in unfit]
    obs.instant("tuning.sweep", **attrs)


def _time_callable(fn: Callable, *args, reps: int = 3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _measure_plan(a, plan: KernelPlan, h, sr, inv_deg=None) -> float:
    """Wall-clock one candidate on its actual dispatch path (the XLA proxy
    on CPU, Pallas on TPU — whatever ``kops`` routes to). Generated kernels
    compute the sum semiring; for mean the timed callable includes the
    cached inverse-degree post-scale — the cost structure the production
    path (``core/spmm._forward``) actually pays for that semiring. The
    packed operand is a jit argument, as in the training step, not a
    constant folded into the timed program."""
    from repro.kernels import ops as kops
    from repro.core import sparse as sp

    if plan.kind == "bsr":
        packed = sp.bsr_from_coo(a, br=plan.br, bc=plan.bc)
        kernel = lambda m, hh: kops.bsr_spmm(       # noqa: E731
            m, hh, fk=plan.fk)[: a.nrows]
    elif plan.kind == "ell":
        packed = sp.ell_from_coo(a)     # full max_deg: plans must be exact
        kernel = kops.ell_spmm
    elif plan.kind == "sell":
        packed = sp.sell_from_coo(a, c=plan.sell_c, sigma=plan.sell_sigma)
        kernel = kops.sell_spmm
    else:
        raise ValueError(plan.kind)

    def timed(m, hh, inv):
        out = kernel(m, hh)
        return out * inv[:, None] if sr.reduce == "mean" else out

    return _time_callable(jax.jit(timed), packed, h, inv_deg)


def _measure_override(a, k: int, plan: KernelPlan, stats: GraphStats, *,
                      hw: HardwareModel | None = None,
                      semiring: str = "sum") -> KernelPlan:
    """Wall-clock trusted vs one candidate per generated family (the
    analytic pick plus the best SELL and the ELL fallback) and keep the
    empirically fastest, updating ``est_*`` with measured seconds.

    ``semiring`` is the reduction the caller will actually run: the trusted
    path is timed with that semiring's own segment reduce, and generated
    candidates include the mean post-scale — so a TuningDB row keyed
    ``(graph, K, semiring)`` stores costs for *its* semiring, not sum's.
    Max/min admit no generated candidates (paper §3.4); their measured row
    is the trusted wall-clock alone."""
    import jax.numpy as jnp
    from repro.core.semiring import get_semiring

    hw = hw or probe_hardware()
    h = jnp.asarray(np.random.default_rng(0).standard_normal(
        (a.ncols, k)).astype(np.float32))
    sr = get_semiring(semiring)
    deg = np.zeros(a.nrows, np.float32)
    np.add.at(deg, np.asarray(a.row)[: a.nse], 1.0)
    degrees = jnp.asarray(deg)
    inv_deg = jnp.asarray(1.0 / np.maximum(deg, 1.0))

    from repro.kernels.ref import spmm_coo_ref
    t_trusted = _time_callable(
        jax.jit(lambda hh: spmm_coo_ref(a, hh, sr, degrees=degrees)), h)

    # Generated candidates obey the same eligibility gates as the analytic
    # sweep (paper §3.2/§3.4): sum/mean semiring AND lane-aligned K. The
    # production dispatch (core/spmm) refuses misaligned-K generated plans,
    # so measuring one here would persist a row production can't honor.
    candidates: list[KernelPlan] = []
    if sr.mxu_eligible and k % hw.lane == 0:
        if plan.kind != "trusted":
            candidates.append(plan)
        if not any(p.kind == "sell" for p in candidates) and stats.sell_counts:
            sells = [KernelPlan(kind="sell", sell_c=c, sell_sigma=s, k_hint=k)
                     for c, s, _ in stats.sell_counts]
            sells = [p for p in sells if plan_fit_error(stats, p, hw) is None]
            if sells:
                candidates.append(min(
                    sells, key=lambda p: estimate_plan_time(stats, k, p, hw)))
        # ELL is measured under the same degree-boundedness gate as the
        # analytic sweep — on a skewed graph the full-max_deg gather it
        # would time is exactly the pathology SELL avoids, so spending GBs
        # to confirm it loses is wasted tuning time.
        ell_bounded = stats.max_deg <= max(4 * stats.avg_deg, 8)
        if ell_bounded and not any(p.kind == "ell" for p in candidates):
            candidates.append(KernelPlan(kind="ell", k_hint=k))
    candidates = [c for c in candidates
                  if plan_fit_error(stats, c, hw) is None]

    timed: list = [("trusted", t_trusted)]
    best, best_t = None, float("inf")
    for cand in candidates:
        t = _measure_plan(a, cand, h, sr, inv_deg=inv_deg)
        timed.append((_plan_label(cand), t))
        if t < best_t:
            best, best_t = cand, t

    if best is not None and best_t <= t_trusted:
        winner = dataclasses.replace(best, est_generated_s=best_t,
                                     est_trusted_s=t_trusted)
    else:
        winner = KernelPlan(kind="trusted", k_hint=k,
                            est_generated_s=best_t, est_trusted_s=t_trusted)
    _log_measured(stats, k, semiring, timed, winner)
    return winner


def _log_measured(stats: GraphStats, k: int, semiring: str, timed: list,
                  winner: KernelPlan) -> None:
    """Emit one ``tuning.measure`` decision event (wall-clock override):
    each timed candidate's measured seconds and the empirical pick."""
    from repro import obs
    obs.metrics().counter("tuning.measured").inc()
    if not obs.enabled():
        return
    obs.instant(
        "tuning.measure",
        graph=f"{stats.nrows}x{stats.ncols}nse{stats.nse}", k=k,
        semiring=semiring, winner=_plan_label(winner),
        candidates=[[name, float(t)] for name, t in timed])


# --------------------------------------------------------------------------
# Tuning curve — the Fig. 2 reproduction
# --------------------------------------------------------------------------

def tuning_curve(a, ks: Sequence[int] = (16, 32, 64, 128, 256, 512, 1024),
                 *, hw: HardwareModel | None = None, measure: bool = False,
                 ) -> list[dict]:
    """Sweep embedding sizes; report generated-vs-trusted speedup per K.

    The peak of this curve is the tuner's "ideal embedding size" (§3.2,
    Fig. 2: 32 on the paper's Intel box, 64 on AMD — hardware-dependent,
    which is the whole point of tuning per platform)."""
    hw = hw or probe_hardware()
    stats = graph_stats(a)
    rows = []
    for k in ks:
        plan = autotune(a, k, hw=hw, measure=measure, stats=stats)
        if measure and plan.est_generated_s != float("inf"):
            speedup = plan.est_trusted_s / plan.est_generated_s
        else:
            t_tr = estimate_plan_time(stats, k, KernelPlan.trusted(k), hw)
            gen = plan if plan.kind != "trusted" else None
            speedup = (t_tr / estimate_plan_time(stats, k, gen, hw)
                       if gen is not None else 1.0)
        rows.append(dict(k=k, kind=plan.kind, br=plan.br, bc=plan.bc,
                         speedup=float(speedup)))
    return rows


def suggest_embedding_size(curve: list[dict]) -> int:
    """The K with the best generated-vs-trusted speedup on a
    :func:`tuning_curve` sweep — the paper's "ideal embedding size" (§3.2,
    hardware-dependent: 32 on the paper's Intel box, 64 on AMD)."""
    return max(curve, key=lambda r: r["speedup"])["k"]


# --------------------------------------------------------------------------
# Tuning DB — persisted tuner decisions (one per (graph fingerprint, K))
# --------------------------------------------------------------------------

class TuningDB:
    """JSON-file store of tuner decisions so repeated runs skip the sweep.

    This is the paper's one-time-tuning amortization: ``build_cached_graph``
    consults the DB before sweeping (and persists what it measures), so the
    expensive ``measure=True`` pass runs once per (graph structure, K) per
    machine, not once per process.

    On-disk format (``_SCHEMA_VERSION`` 2): ``{"schema": 2, "plans": {...}}``.
    Legacy flat dicts (pre-schema) still load. A corrupt or
    incompatible-schema file is *quarantined* — renamed to
    ``<path>.corrupt`` with a warning — rather than silently discarded, so
    measured plans are never destroyed without a trace (the quarantined
    file stays recoverable by hand)."""

    _SCHEMA_VERSION = 2

    def __init__(self, path: str | None = None):
        self.path = path or os.environ.get(
            "REPRO_TUNING_DB", os.path.expanduser("~/.repro_tuning.json"))
        self._db: dict[str, dict] = self._load(self.path)

    @classmethod
    def _load(cls, path: str) -> dict[str, dict]:
        if not os.path.exists(path):
            return {}
        try:
            # A zero-length file (fresh touch, or /dev/null used as an
            # always-empty store) is an empty DB, not corruption.
            if os.path.getsize(path) == 0:
                return {}
            with open(path) as f:
                raw = json.load(f)
            if not isinstance(raw, dict):
                raise ValueError(f"expected a JSON object, got {type(raw)}")
            if "schema" in raw:
                if raw["schema"] != cls._SCHEMA_VERSION or \
                        not isinstance(raw.get("plans"), dict):
                    raise ValueError(
                        f"unsupported TuningDB schema {raw.get('schema')!r} "
                        f"(this build reads {cls._SCHEMA_VERSION})")
                return raw["plans"]
            # legacy flat dict-of-plan-dicts (pre-schema format)
            return raw
        except (json.JSONDecodeError, ValueError, OSError) as exc:
            quarantine = path + ".corrupt"
            try:
                os.replace(path, quarantine)
                where = f"quarantined to {quarantine}"
            except OSError:
                where = "left in place"
            warnings.warn(
                f"TuningDB at {path} is unreadable ({exc}); {where}. "
                f"Starting with an empty DB — measured plans in the old "
                f"file are preserved there, not overwritten.")
            return {}

    def __len__(self) -> int:
        return len(self._db)

    @staticmethod
    def key(a, k: int, semiring: str = "sum") -> str:
        """Structural fingerprint of (graph, K, semiring). Stable across
        equivalent graphs (same sparsity pattern — values don't matter to
        the plan) and collision-resistant across different structures of the
        same size via a CRC over the sorted edge list. Sum-semiring keys
        carry no suffix, so rows persisted before per-semiring tuning keep
        resolving; mean/max/min get their own rows (their measured costs
        include the post-scale / segment reduce — see
        :func:`_measure_override`)."""
        import zlib
        row = np.asarray(a.row)[: a.nse]
        col = np.asarray(a.col)[: a.nse]
        order = np.lexsort((col, row))   # storage-order independent
        row = np.ascontiguousarray(row[order], np.int32)
        col = np.ascontiguousarray(col[order], np.int32)
        fp = zlib.crc32(col.tobytes(), zlib.crc32(row.tobytes()))
        sfx = "" if semiring == "sum" else f"sr{semiring}"
        return f"{a.nrows}x{a.ncols}nse{a.nse}fp{fp:08x}k{k}{sfx}"

    def get(self, a, k: int, semiring: str = "sum") -> KernelPlan | None:
        """Previously persisted plan for (graph ``a``, width ``k``,
        ``semiring``), or None — a miss means the caller should run the
        sweep and ``put``."""
        return self.get_key(self.key(a, k, semiring))

    def put(self, a, k: int, plan: KernelPlan,
            semiring: str = "sum") -> None:
        """Record a tuner decision in memory; ``save()`` persists it."""
        self.put_key(self.key(a, k, semiring), plan)

    # Generic string-keyed rows: callers that tune per *shape bucket*
    # rather than per concrete graph (repro.sampling's block packing —
    # every minibatch has a fresh edge set, so a structural CRC would
    # never hit) bring their own key format.
    def get_key(self, key: str) -> KernelPlan | None:
        d = self._db.get(key)
        return KernelPlan.from_json(d) if d else None

    def put_key(self, key: str, plan: KernelPlan) -> None:
        self._db[key] = plan.to_json()

    def save(self) -> None:
        """Atomically write the DB to ``self.path`` (tmp file + rename, so
        a crashed run never leaves a half-written store behind). Writes the
        versioned ``{"schema": N, "plans": ...}`` envelope."""
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"schema": self._SCHEMA_VERSION, "plans": self._db},
                      f, indent=1)
        os.replace(tmp, self.path)
