"""GPipe-style pipeline parallelism over a stage-sharded parameter stack.

``pipeline_apply`` runs ``fn`` (one stage's computation) S times over a
(S, ...) parameter stack whose leading dim is sharded over the pipeline mesh
axis — stage s's weights live only on device s. Microbatches stream through
the ring: at step t device i computes microbatch ``t - i`` (when in range)
and hands its activation to device i+1 via ``ppermute``; the pipeline fills
for S-1 steps, runs full, and drains for S-1 steps, so bubble fraction is
(S-1)/(S-1+M) — more microbatches amortize it. Schedule variants (1F1B,
interleaved) are ROADMAP items; this is the forward schedule the multi-pod
dry-run needs.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = Any

__all__ = ["pipeline_apply"]


def _pipeline_axis(mesh: Mesh) -> str:
    return "pipe" if "pipe" in mesh.shape else next(iter(mesh.shape))


def pipeline_apply(fn: Callable[[Array, Array], Array], mesh: Mesh,
                   params: Array, x: Array, microbatches: int = 4) -> Array:
    """y = fn(params[S-1], ... fn(params[1], fn(params[0], x))).

    ``params``: (S, ...) stage stack, S = size of the pipeline axis;
    ``x``: (B, ...) with B divisible by ``microbatches``. Returns (B, ...),
    replicated (every device holds the drained outputs).
    """
    axis = _pipeline_axis(mesh)
    s = int(mesh.shape[axis])
    assert params.shape[0] == s, (params.shape, s)
    b = x.shape[0]
    m = microbatches
    assert b % m == 0, (b, m)
    mb = x.reshape(m, b // m, *x.shape[1:])

    def body(w_stk, mb):
        w = w_stk[0]                               # this device's stage
        me = jax.lax.axis_index(axis)
        shift = [(i, (i + 1) % s) for i in range(s)]

        def step(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (clamped during drain: its
            # results past m never reach the last stage inside the window)
            feed = mb[jnp.clip(t, 0, m - 1)]
            cur = jnp.where(me == 0, feed, buf)
            y = fn(w, cur)
            slot = t - (s - 1)                      # drains at the last stage
            take = (slot >= 0) & (slot < m) & (me == s - 1)
            outs = jnp.where(take,
                             outs.at[jnp.clip(slot, 0, m - 1)].set(y), outs)
            return jax.lax.ppermute(y, axis, shift), outs

        init = (jnp.zeros_like(mb[0]), jnp.zeros_like(mb))
        _, outs = jax.lax.fori_loop(0, s + m - 1, step, init)
        # replicate the drained outputs (only the last stage holds them)
        return jax.lax.psum(jnp.where(me == s - 1, outs, 0), axis)

    n_extra = params.ndim - 1
    y = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, *([None] * n_extra)),
                  P(*([None] * mb.ndim))),
        out_specs=P(*([None] * mb.ndim)), check_vma=False,
    )(params, mb)
    return y.reshape(b, *x.shape[1:])
