"""Production mesh builders (moved from repro.launch.mesh — that module
re-exports these for back-compat).

Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import; everything
else sees the 1-device CPU default).

Every mesh is built with ``Auto`` axis types: the sharding rules
(``with_sharding_constraint`` over named axes) and the ``shard_map``
bodies that close over mesh-placed operands are written for GSPMD-style
axes, and ``jax.make_mesh`` otherwise defaults to ``Explicit`` axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "make_grid_mesh",
           "make_data_mesh", "axis_shard_count", "replicated_sharding",
           "leading_axis_sharding", "replicated_device_put"]


def _auto_mesh(shape, names):
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names))


def axis_shard_count(mesh, axis: str = "data") -> int:
    """Size of a named mesh axis, with "axis not in this mesh" reading as
    one shard — the contract seed-sharding (repro.sampling.loader) and
    other data-parallel consumers rely on to run unchanged on a
    single-device mesh."""
    try:
        return int(mesh.shape[axis])
    except (KeyError, TypeError):
        return 1


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods).

    Axis semantics: 'pod' = cross-pod data parallel (slow links — candidates
    for gradient compression), 'data' = in-pod data parallel / FSDP,
    'model' = tensor/expert parallel (fast ICI).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return _auto_mesh((data, model), ("data", "model"))


def make_data_mesh(data: int | None = None, *, model: int = 1):
    """('data', 'model') mesh with an explicit data-parallel degree.

    The mesh the lockstep minibatch trainer and the shard_map LM step
    expect: ``data`` shards walk the seed/batch stream in lockstep and
    psum gradients; ``model`` is along for tensor-parallel composition
    (params replicate over it in pure data-parallel mode). Defaults to
    all devices on the data axis."""
    n = len(jax.devices())
    data = max(n // model, 1) if data is None else data
    assert data * model <= n, (data, model, n)
    return _auto_mesh((data, model), ("data", "model"))


def replicated_sharding(mesh):
    """Fully-replicated NamedSharding on ``mesh`` — what the trainer uses
    to ``device_put`` big read-only operands (the feature matrix) once,
    instead of baking them into every jit trace as constants."""
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec())


def replicated_device_put(x, mesh=None):
    """``device_put`` with mesh-replicated placement when a mesh is given,
    plain default-device placement otherwise — the one-liner every
    device-resident singleton (the sampling graph topology, the serving
    feature-cache table) uses so single-device code and mesh code share a
    placement path."""
    if mesh is None:
        return jax.device_put(x)
    return jax.device_put(x, replicated_sharding(mesh))


def leading_axis_sharding(mesh, axis: str = "data"):
    """NamedSharding splitting dim 0 over ``axis`` — the placement for
    host-stacked per-shard batches feeding a ``shard_map`` over ``axis``
    (each device holds only its own shard's slice, never the full
    stack)."""
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec(axis))


def make_grid_mesh(devices: int | None = None):
    """(pr x pc) ('row', 'col') sub-mesh for the 2-D vertex-cut GNN path.

    Picks the most square factorization of the device count (pr = the
    largest divisor <= sqrt(P)), which is what makes the per-device
    communication O(N/sqrt(P)) — see dist/gnn2d.py. A square count (4, 16,
    64, 256 chips) yields the exact sqrt(P) x sqrt(P) grid."""
    n = devices if devices is not None else len(jax.devices())
    pr = max(int(n ** 0.5), 1)
    while n % pr:
        pr -= 1
    return _auto_mesh((pr, n // pr), ("row", "col"))
