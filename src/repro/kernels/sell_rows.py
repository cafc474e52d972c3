"""Row broadcasts and row reductions over a SELL-C table's steps (Pallas TPU
kernels), and the moves between the table's flat slot order and its
steps-on-lanes view.

The GAT softmax (``core/fusedmm.py``) keeps per-slot values of a SELL table
as ``(H, C, n_steps)``: channel ``(h, j)`` on sublanes, step ``t`` on lanes,
so that lane ``t`` of channel ``j`` is the slot of row ``slice_of[t] * C +
j``. Per-row values are ``(H * C, nslices)``. ``slice_of`` is sorted, so
the steps of one 128-lane group span at most 128 consecutive slices, and a
group's rows lie in a window of two lane tiles from the group's first
slice rounded down to 128 (``_bases``, one scalar per group). Every kernel
reads one slice id per step, shared by all channels; none reads a per-slot
index:

* ``row_bcast``: per group, lane gathers (``take_along_axis``) from the
  window's two tiles, selected by the slice id's offset;
* ``row_reduce`` (``sum`` or ``max``): per group, a segmented inclusive
  scan over the lanes (log-step rolls, combining lanes of the same slice),
  then each window lane takes the lane that ends its slice in the group,
  and is combined into the ``(H * C, nslices)`` output, which stays in
  VMEM across the sequential grid (a slice may span many groups). The max
  is exact; a sum changes only its order;
* ``to_steps`` / ``to_flat``: 128 steps of one head are ``C`` flat rows of
  128 lanes (``128 / C`` steps of ``C`` slots each) or one lane tile of the
  ``C`` channels; each maps to the other by lane gathers and selects, exact.

Layout constraints: ``C`` divides 128 and is a multiple of 8 (the tuner's
8, 16 and 32), and the per-row array fits VMEM (``fits``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["to_steps", "to_flat", "row_bcast", "row_reduce", "fits"]

LANES = 128
BLOCK = 2048                   # steps per grid step
GROUPS = BLOCK // LANES        # 128-step lane groups per grid step
_FAR = 1 << 30                 # slice id of steps past the table's end
_VMEM_LIMIT = 64 * 1024 * 1024
_ROWS_BUDGET = 16 * 1024 * 1024   # bytes of a VMEM-resident per-row array


def fits(c: int, rows: int, nslices: int) -> bool:
    """Whether the kernels take a table of slice height ``c`` with ``rows``
    channels (heads times ``c``) over ``nslices`` slices."""
    return (c % 8 == 0 and LANES % c == 0
            and rows * _padded(nslices) * 4 <= _ROWS_BUDGET)


def _padded(nslices: int) -> int:
    """Lanes of a per-row array: whole tiles, plus the tile a window past
    the last group's base reads."""
    return (-(-nslices // LANES) + 1) * LANES


def _bases(slice_of, n_blocks: int):
    """Per 128-step group, its first step's slice rounded down to 128."""
    b = slice_of[::LANES] // LANES * LANES
    return jnp.pad(b, (0, n_blocks * GROUPS - b.shape[0]), mode="edge")


def _params(semantics: str):
    return pltpu.CompilerParams(dimension_semantics=(semantics,),
                                vmem_limit_bytes=_VMEM_LIMIT)


def _group(g):
    return pl.ds(pl.multiple_of(g * LANES, LANES), LANES)


# ------------------------------------------------------------- layout moves

def _to_steps_kernel(x_ref, o_ref, *, c, heads):
    spr = LANES // c                      # steps per flat row
    sub = lax.broadcasted_iota(jnp.int32, (c, LANES), 0)
    lane = lax.broadcasted_iota(jnp.int32, (c, LANES), 1)
    pick = sub == lane // spr             # flat row i holds steps i*spr..
    src = lane % spr * c                  # step's slot j at lane src + j

    def body(q, carry):
        rows = pl.ds(pl.multiple_of(q * LANES * c, LANES * c), LANES * c)
        for h in range(heads):
            xq = x_ref[pl.ds(h, 1), rows].reshape(c, LANES)
            for j in range(c):
                g = jnp.take_along_axis(xq, src + j, axis=1)
                o_ref[h, pl.ds(j, 1), _group(q)] = jnp.sum(
                    jnp.where(pick, g, 0), axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, GROUPS, body, 0)


def to_steps(x, c: int, n_steps: int, *, interpret: bool = False):
    """``(H, n_steps * c)`` in table order to ``(H, c, n_steps)``."""
    heads = x.shape[0]
    return pl.pallas_call(
        functools.partial(_to_steps_kernel, c=c, heads=heads),
        grid=(pl.cdiv(n_steps, BLOCK),),
        in_specs=[pl.BlockSpec((heads, BLOCK * c), lambda i: (0, i))],
        out_specs=pl.BlockSpec((heads, c, BLOCK), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((heads, c, n_steps), x.dtype),
        compiler_params=_params("parallel"), interpret=interpret)(x)


def _to_flat_kernel(x_ref, o_ref, *, c, heads):
    spr = LANES // c
    sub = lax.broadcasted_iota(jnp.int32, (c, LANES), 0)
    lane = lax.broadcasted_iota(jnp.int32, (c, LANES), 1)
    src = sub * spr + lane // c           # the flat lane's step in the group
    slot = lane % c                       # and its slot of that step

    def body(q, carry):
        rows = pl.ds(pl.multiple_of(q * LANES * c, LANES * c), LANES * c)
        for h in range(heads):
            aq = x_ref[h, :, _group(q)]
            out = jnp.zeros((c, LANES), x_ref.dtype)
            for j in range(c):
                g = jnp.take_along_axis(
                    jnp.broadcast_to(aq[j:j + 1], (c, LANES)), src, axis=1)
                out = jnp.where(slot == j, g, out)
            o_ref[pl.ds(h, 1), rows] = out.reshape(1, LANES * c)
        return carry

    lax.fori_loop(0, GROUPS, body, 0)


def to_flat(x, *, interpret: bool = False):
    """``(H, c, n_steps)`` back to ``(H, n_steps * c)`` in table order."""
    heads, c, n_steps = x.shape
    return pl.pallas_call(
        functools.partial(_to_flat_kernel, c=c, heads=heads),
        grid=(pl.cdiv(n_steps, BLOCK),),
        in_specs=[pl.BlockSpec((heads, c, BLOCK), lambda i: (0, 0, i))],
        out_specs=pl.BlockSpec((heads, BLOCK * c), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((heads, n_steps * c), x.dtype),
        compiler_params=_params("parallel"), interpret=interpret)(x)


# ---------------------------------------------------------------- row ops

def _bcast_kernel(base_ref, ids_ref, y_ref, o_ref):
    i = pl.program_id(0)
    rows = o_ref.shape[0]

    def body(g, carry):
        b = pl.multiple_of(base_ref[i * GROUPS + g], LANES)
        k = jnp.broadcast_to(ids_ref[:, _group(g)] - b, (rows, LANES))
        at = k & (LANES - 1)
        lo = jnp.take_along_axis(y_ref[:, pl.ds(b, LANES)], at, axis=1)
        hi = jnp.take_along_axis(y_ref[:, pl.ds(b + LANES, LANES)], at,
                                 axis=1)
        o_ref[:, _group(g)] = jnp.where(k < LANES, lo, hi)
        return carry

    lax.fori_loop(0, GROUPS, body, 0)


def row_bcast(y, slice_of, n_steps: int, *, interpret: bool = False):
    """Per-row values ``y`` ``(R, nslices)`` to every step: ``(R,
    n_steps)`` whose column ``t`` is ``y[:, slice_of[t]]``."""
    rows, nslices = y.shape
    n_blocks = pl.cdiv(n_steps, BLOCK)
    width = _padded(nslices)
    y = jnp.pad(y, ((0, 0), (0, width - nslices)))
    return pl.pallas_call(
        _bcast_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_blocks,),
            in_specs=[pl.BlockSpec((1, BLOCK), lambda i, b: (0, i)),
                      pl.BlockSpec((rows, width), lambda i, b: (0, 0))],
            out_specs=pl.BlockSpec((rows, BLOCK), lambda i, b: (0, i))),
        out_shape=jax.ShapeDtypeStruct((rows, n_steps), y.dtype),
        compiler_params=_params("parallel"), interpret=interpret,
    )(_bases(slice_of, n_blocks), slice_of.reshape(1, -1), y)


def _reduce_kernel(base_ref, ids_ref, x_ref, o_ref, *, kind, n_steps):
    i = pl.program_id(0)
    rows = x_ref.shape[0]
    ident = -jnp.inf if kind == "max" else 0.0
    op = jnp.maximum if kind == "max" else jnp.add

    @pl.when(i == 0)
    def _():
        o_ref[...] = jnp.full(o_ref.shape, ident, o_ref.dtype)

    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def body(g, carry):
        b = pl.multiple_of(base_ref[i * GROUPS + g], LANES)
        live = i * BLOCK + g * LANES + lane < n_steps
        ids = jnp.where(live, ids_ref[:, _group(g)], _FAR)
        x = jnp.where(live, x_ref[:, _group(g)], ident)
        # segmented inclusive scan: each lane ends up holding its slice's
        # reduction from the slice's first lane in the group to itself
        shift = 1
        while shift < LANES:
            behind = pltpu.roll(lane, shift, 1) == lane - shift
            same = behind & (pltpu.roll(ids, shift, 1) == ids)
            x = jnp.where(same, op(x, pltpu.roll(x, shift, 1)), x)
            shift *= 2
        ids8 = jnp.broadcast_to(ids, (8, LANES))
        col = jnp.transpose(ids8)[:, :1]
        for half in range(2):
            at = pl.ds(pl.multiple_of(b + half * LANES, LANES), LANES)
            want = b + half * LANES + lane          # the window's slices
            last = jnp.sum((col <= want).astype(jnp.int32), axis=0,
                           keepdims=True) - 1       # last lane at or below
            src = jnp.maximum(last, 0)
            found = jnp.take_along_axis(
                ids8, jnp.broadcast_to(src, (8, LANES)), axis=1)[:1]
            hit = (last >= 0) & (found == want)
            part = jnp.take_along_axis(
                x, jnp.broadcast_to(src, (rows, LANES)), axis=1)
            cur = o_ref[:, at]
            o_ref[:, at] = jnp.where(hit, op(cur, part), cur)
        return carry

    lax.fori_loop(0, GROUPS, body, 0)


def row_reduce(x, slice_of, nslices: int, kind: str, *,
               interpret: bool = False):
    """The ``sum`` or ``max`` of ``x`` ``(R, n_steps)`` over each slice's
    steps: ``(R, nslices)``."""
    rows, n_steps = x.shape
    n_blocks = pl.cdiv(n_steps, BLOCK)
    width = _padded(nslices)
    out = pl.pallas_call(
        functools.partial(_reduce_kernel, kind=kind, n_steps=n_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_blocks,),
            in_specs=[pl.BlockSpec((1, BLOCK), lambda i, b: (0, i)),
                      pl.BlockSpec((rows, BLOCK), lambda i, b: (0, i))],
            out_specs=pl.BlockSpec((rows, width), lambda i, b: (0, 0))),
        out_shape=jax.ShapeDtypeStruct((rows, width), x.dtype),
        compiler_params=_params("arbitrary"), interpret=interpret,
    )(_bases(slice_of, n_blocks), slice_of.reshape(1, -1), x)
    return out[:, :nslices]
