"""Device-side fused k-hop sampling primitives (GraphBolt-style).

The host ``NeighborSampler`` does rank-select + gather in numpy; these are
the same per-hop primitives as device kernels, so the minibatch hot path
(``train/gnn_minibatch`` with ``sampler="device"``) can fuse sample + pack
+ step into one jitted program:

* :func:`segment_sample` — per-frontier-row neighbor *rank* selection into
  a dense ``(F, width)`` slot table. Randomness is a **counter-based
  stateless RNG**: every draw is a pure integer hash of ``(seed, round,
  hop, node id, slot)`` (splitmix-style avalanche, exact float32
  bit-to-uniform), so draws are bitwise-deterministic per key, independent
  of batch composition, and identical between the XLA reference and the
  Pallas kernel — no RNG stream threading, matching the host sampler's
  determinism contract (the *stream* differs from numpy's; see
  docs/architecture.md).
* :func:`expand_indptr` — turns ranks into flat CSR positions
  (``indptr[row] + rank``), routing invalid slots to a sentinel position
  (the GraphBolt ``expand_indptr`` analog, shapes static).
* :func:`flat_gather` — ``arr[pos]`` for a flat device-resident array; the
  Pallas path DMAs the 128-lane row of the reshaped array that holds each
  position (the GraphBolt ``index_select`` analog).

Each primitive follows the ``kernels/ops`` backend policy: Pallas kernel on
TPU, an XLA path with the same algorithm elsewhere, ``interpret=True``
forcing the Pallas body through the interpreter for correctness tests. The
without-replacement draw is a partial virtual Fisher–Yates (``fanout``
steps over a virtual ``[0, deg)`` permutation with an O(fanout) override
table), which keeps shapes static, is exactly uniform without replacement,
and costs O(F * fanout^2) integer ops per hop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import on_tpu

__all__ = [
    "segment_sample",
    "sample_valid_mask",
    "expand_indptr",
    "flat_gather",
]

_ROW_TILE = 8      # frontier rows per Pallas grid step (one sublane tile)


# --------------------------------------------------------------------------
# Counter-based stateless RNG (shared bit-exactly by XLA and Pallas paths)
# --------------------------------------------------------------------------

def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """splitmix32-style avalanche on uint32 (wrapping arithmetic)."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x

def _edge_bits(seed: int, rnd, hop: int, gid, slot) -> jnp.ndarray:
    """uint32 hash of the draw counter (seed, round, hop, node, slot).
    ``seed``/``hop`` are static; ``rnd``/``gid``/``slot`` may be traced and
    broadcast against each other."""
    h = _mix32(jnp.uint32(seed) ^ jnp.uint32(0x9E3779B9))
    h = _mix32(h ^ jnp.asarray(rnd).astype(jnp.uint32))
    h = _mix32(h ^ jnp.uint32(hop))
    h = _mix32(h ^ jnp.asarray(gid).astype(jnp.uint32))
    h = _mix32(h ^ jnp.asarray(slot).astype(jnp.uint32))
    return h


def _bits_to_uniform(bits: jnp.ndarray) -> jnp.ndarray:
    """Exact [0, 1) float32 from the top 24 bits — every step (shift, int
    -> f32 of a 24-bit value, power-of-two scale) is exact, so the uniform
    is bit-identical wherever the hash is. The shifted value is below 2**24,
    so routing it through int32 keeps every bit; the TPU has no direct
    uint32 -> float32 conversion."""
    top = (bits >> jnp.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(2.0 ** -24)


# --------------------------------------------------------------------------
# The rank-select body (one tile or the full frontier — same math)
# --------------------------------------------------------------------------

def _select_ranks(deg, gid, rnd, *, width: int, fanout, seed: int, hop: int,
                  replace: bool) -> jnp.ndarray:
    """(F, width) int32 neighbor ranks for frontier rows with in-degree
    ``deg``. Runs identically on the full arrays (XLA path) and on a row
    tile inside the Pallas kernel — pure elementwise/rowwise jnp ops, with
    every per-row quantity kept as an (F, 1) column so the TPU never has
    to relayout a reduced vector into a broadcast."""
    deg = deg.reshape(-1, 1)
    gid = gid.reshape(-1, 1)
    f = deg.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (f, width), 1)
    if fanout is None:                      # full neighborhood: identity
        return iota
    # Derive the slot index from the (row-varying) degrees: a row-invariant
    # constant is laid out replicated across sublanes on the TPU, and the
    # Fisher–Yates carries below could not be relaid back to that form.
    iota = jnp.where(deg >= 0, iota, 0)

    if replace:
        bits = _edge_bits(seed, rnd, hop, gid, iota)
        u = _bits_to_uniform(bits)
        r = jnp.floor(u * deg.astype(jnp.float32)).astype(jnp.int32)
        return jnp.minimum(r, jnp.maximum(deg - 1, 0))

    # Without replacement: virtual Fisher–Yates over [0, deg). Step j draws
    # r in [j, deg) and swap-reads through an O(width) override table
    # (keys/vals) instead of materializing the permutation — exact uniform
    # sampling of `width` distinct ranks with static shapes.
    degf = deg.astype(jnp.float32)

    def lookup(keys, vals, key):
        """overrides.get(key, key): latest slot whose key matches."""
        slot = jnp.max(jnp.where(keys == key, iota, -1), axis=1,
                       keepdims=True)
        v = jnp.sum(jnp.where(iota == slot, vals, 0), axis=1, keepdims=True)
        return jnp.where(slot >= 0, v, key)

    def fy_step(j, carry):
        keys, vals, out = carry
        u = _bits_to_uniform(_edge_bits(seed, rnd, hop, gid, j))  # (F, 1)
        span = degf - j.astype(jnp.float32)
        r = j + jnp.minimum(jnp.floor(u * span).astype(jnp.int32),
                            jnp.maximum(deg - j - 1, 0))
        v_r = lookup(keys, vals, r)
        v_j = lookup(keys, vals, jnp.broadcast_to(j, r.shape))
        col_j = iota == j
        keys = jnp.where(col_j, r, keys)
        vals = jnp.where(col_j, v_j, vals)
        out = jnp.where(col_j, v_r, out)
        return keys, vals, out

    keys0 = iota * 0 - 1
    vals0 = iota * 0
    _, _, fy = jax.lax.fori_loop(0, width, fy_step, (keys0, vals0, iota))
    # rows with deg <= width keep all their edges (identity ranks)
    return jnp.where(deg > width, fy, iota)


def sample_valid_mask(deg, *, width: int, fanout, replace: bool = False
                      ) -> jnp.ndarray:
    """(F, width) bool — which slots of the rank table are real draws.
    Pure function of the degrees (no randomness): full-neighbor and
    without-replacement rows fill ``min(deg, width)`` leading slots;
    with-replacement rows fill all ``width`` slots whenever ``deg > 0``."""
    f = deg.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (f, width), 1)
    if fanout is not None and replace:
        return jnp.broadcast_to((deg > 0)[:, None], (f, width))
    lim = deg if fanout is None else jnp.minimum(deg, width)
    return iota < lim[:, None]


# --------------------------------------------------------------------------
# segment_sample — dispatcher + Pallas kernel
# --------------------------------------------------------------------------

def _segment_sample_pallas(deg, gid, rnd, *, width, fanout, seed, hop,
                           replace, interpret):
    f = deg.shape[0]
    fp = -(-f // _ROW_TILE) * _ROW_TILE
    deg2 = jnp.pad(deg.reshape(-1, 1), ((0, fp - f), (0, 0)))
    gid2 = jnp.pad(gid.reshape(-1, 1), ((0, fp - f), (0, 0)))
    rnd_arr = jnp.asarray(rnd).reshape(1).astype(jnp.int32)

    def kernel(rnd_ref, deg_ref, gid_ref, out_ref):
        out_ref[...] = _select_ranks(
            deg_ref[...], gid_ref[...], rnd_ref[0], width=width,
            fanout=fanout, seed=seed, hop=hop, replace=replace)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,          # the traced round counter
            grid=(fp // _ROW_TILE,),
            in_specs=[
                pl.BlockSpec((_ROW_TILE, 1), lambda i, rnd: (i, 0)),   # deg
                pl.BlockSpec((_ROW_TILE, 1), lambda i, rnd: (i, 0)),   # gid
            ],
            out_specs=pl.BlockSpec((_ROW_TILE, width), lambda i, rnd: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((fp, width), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(rnd_arr, deg2.astype(jnp.int32), gid2.astype(jnp.int32))
    return out[:f]


def segment_sample(deg, gid, rnd, *, width: int, fanout, seed: int = 0,
                   hop: int = 0, replace: bool = False,
                   interpret: bool | None = None) -> jnp.ndarray:
    """(F, width) int32 per-row neighbor ranks (see module docstring).

    ``deg``/``gid`` are the frontier's in-degrees and global node ids;
    ``rnd`` is the (traced) round counter; ``width`` is the static slot
    count (the fanout, or the graph max degree for ``fanout=None``). Slots
    beyond :func:`sample_valid_mask` hold junk ranks — callers mask.
    Bitwise identical between the XLA and Pallas paths by construction."""
    deg = deg.astype(jnp.int32)
    gid = gid.astype(jnp.int32)
    if fanout is None:      # no randomness: identity ranks on either path
        return jax.lax.broadcasted_iota(jnp.int32, (deg.shape[0], width), 1)
    use_pallas = on_tpu() if interpret is None else True
    if use_pallas:
        return _segment_sample_pallas(deg, gid, rnd, width=width,
                                      fanout=fanout, seed=seed, hop=hop,
                                      replace=replace,
                                      interpret=bool(interpret))
    return _select_ranks(deg, gid, rnd, width=width, fanout=fanout,
                         seed=seed, hop=hop, replace=replace)


# --------------------------------------------------------------------------
# expand_indptr — ranks -> flat CSR positions
# --------------------------------------------------------------------------

def _expand_indptr_pallas(start, ranks, vmask, *, sentinel, interpret):
    f, width = ranks.shape
    fp = -(-f // _ROW_TILE) * _ROW_TILE
    pad = ((0, fp - f), (0, 0))
    start2 = jnp.pad(start.reshape(-1, 1), pad)

    def kernel(start_ref, ranks_ref, mask_ref, out_ref):
        pos = start_ref[:, 0][:, None] + ranks_ref[...]
        out_ref[...] = jnp.where(mask_ref[...] != 0, pos,
                                 jnp.int32(sentinel))

    out = pl.pallas_call(
        kernel,
        grid=(fp // _ROW_TILE,),
        in_specs=[
            pl.BlockSpec((_ROW_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((_ROW_TILE, width), lambda i: (i, 0)),
            pl.BlockSpec((_ROW_TILE, width), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROW_TILE, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((fp, width), jnp.int32),
        interpret=interpret,
    )(start2.astype(jnp.int32), jnp.pad(ranks, pad),
      jnp.pad(vmask.astype(jnp.int32), pad))
    return out[:f]


def expand_indptr(start, ranks, valid, *, sentinel: int,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Flat CSR positions ``start[row] + rank`` for every valid slot;
    invalid slots route to the static ``sentinel`` position (callers keep
    an inert entry there — id ``num_nodes``, value 0)."""
    use_pallas = on_tpu() if interpret is None else True
    if use_pallas:
        return _expand_indptr_pallas(start.astype(jnp.int32), ranks,
                                     valid, sentinel=sentinel,
                                     interpret=bool(interpret))
    pos = start.astype(jnp.int32)[:, None] + ranks
    return jnp.where(valid, pos, jnp.int32(sentinel))


# --------------------------------------------------------------------------
# flat_gather — arr[pos] with one 128-lane row DMA per position
# --------------------------------------------------------------------------

def _flat_gather_pallas(arr, pos, *, interpret):
    lane = 128
    tile = _ROW_TILE * lane             # positions per grid step
    n = arr.shape[0]
    arr2 = jnp.pad(arr, (0, -n % lane)).reshape(-1, lane)
    f, width = pos.shape
    npos = f * width
    pos2 = jnp.pad(pos.reshape(-1).astype(jnp.int32),
                   (0, -npos % tile)).reshape(-1, lane)

    def kernel(pos_ref, arr_hbm, out_ref, rows, sem):
        for q in range(_ROW_TILE):       # one 128-position row at a time
            def issue(i, c, q=q):
                blk = pos_ref[q, i] // lane
                pltpu.make_async_copy(arr_hbm.at[pl.ds(blk, 1)],
                                      rows.at[pl.ds(i, 1)], sem.at[0]).start()
                return c

            def drain(i, c):
                pltpu.make_async_copy(arr_hbm.at[pl.ds(0, 1)],
                                      rows.at[pl.ds(0, 1)], sem.at[0]).wait()
                return c

            def pick(i, c, q=q):
                out_ref[q, i] = rows[i, pos_ref[q, i] % lane]
                return c

            jax.lax.fori_loop(0, lane, issue, 0)
            jax.lax.fori_loop(0, lane, drain, 0)
            jax.lax.fori_loop(0, lane, pick, 0)

    smem_tile = pl.BlockSpec((_ROW_TILE, lane), lambda i: (i, 0),
                             memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel,
        grid=(pos2.shape[0] // _ROW_TILE,),
        in_specs=[smem_tile, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=smem_tile,
        out_shape=jax.ShapeDtypeStruct(pos2.shape, arr.dtype),
        scratch_shapes=[pltpu.SMEM((lane, lane), arr.dtype),
                        pltpu.SemaphoreType.DMA((1,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pos2, arr2)
    return out.reshape(-1)[:npos].reshape(f, width)


def flat_gather(arr, pos, *, interpret: bool | None = None) -> jnp.ndarray:
    """``arr[pos]`` for a 1-D device array and an (F, width) position
    table (positions must be in range — the sampling path guarantees this
    via the ``expand_indptr`` sentinel). Pallas: each grid step takes 1024
    positions as an SMEM block, DMAs the 128-lane row of the reshaped
    array that holds each element into SMEM, and picks the lane — the
    GraphBolt ``index_select`` pattern with no per-position table in
    scalar prefetch. XLA: one fused gather."""
    use_pallas = on_tpu() if interpret is None else True
    if use_pallas:
        return _flat_gather_pallas(arr, pos, interpret=bool(interpret))
    return jnp.take(arr, pos, mode="clip")
