"""repro.dist — the distributed-execution subsystem.

Every sharding concern lives here; model/train/launch code depends on this
package and nothing else for distribution. The organizing idea is a
two-level naming scheme:

1. **Logical axes.** Model code annotates tensors with semantic axis names
   (``shard_constraint(x, ("batch", "seq", "d_model"))``) and parameter
   leaves are classified by path into logical-axis tuples
   (:func:`repro.dist.partition.param_logical_axes`). Model code never
   mentions a mesh axis.

2. **Rules.** A :class:`repro.dist.sharding.Rules` table maps each logical
   axis to an ordered tuple of *candidate* mesh axes (``"batch" -> ('pod',
   'data')``; ``"d_ff" -> ('model',)``). Resolution intersects candidates
   with the mesh active via ``with mesh:`` — axes missing from the mesh,
   already used by an earlier dim of the same tensor, or not dividing the
   dim are skipped — so one rule set serves the 2x16x16 multi-pod mesh, a
   2x2 test mesh, and (as a strict no-op) single-device CPU. Rule sets are
   activated with ``use_rules(...)`` and varied with ``Rules.override``
   (e.g. ``LM_RULES.override(seq="model")`` = sequence parallelism).

Modules:

* :mod:`~repro.dist.sharding`    rules, ``use_rules``, ``shard_constraint``
* :mod:`~repro.dist.partition`   ``LM_RULES`` + param/state/batch/cache
  ``NamedSharding`` builders
* :mod:`~repro.dist.mesh`        production/test mesh constructors
* :mod:`~repro.dist.collectives` ``compressed_psum`` (int8 cross-pod
  gradient reduce), ``compressed_psum_scatter``, ``ring_allgather_matmul``
* :mod:`~repro.dist.gnn`         1-D row-partitioned graphs + halo'd
  distributed SpMM
* :mod:`~repro.dist.gnn2d`       2-D vertex-cut tile grid: O(N/sqrt(P))
  distributed SpMM
* :mod:`~repro.dist.pipeline`    GPipe-style microbatch pipeline
"""
from __future__ import annotations

from repro.dist.collectives import (compressed_psum, compressed_psum_scatter,
                                    ring_allgather_matmul, sync_grads,
                                    wire_bytes)
from repro.dist.gnn import (DistGraph, build_dist_graph, comm_volume,
                            distributed_spmm)
from repro.dist.gnn2d import (Graph2D, comm_volume_2d, distributed_spmm_2d,
                              partition_2d, scores_to_dense)
from repro.dist.mesh import (leading_axis_sharding, make_data_mesh,
                             make_grid_mesh, make_local_mesh,
                             make_production_mesh, replicated_sharding)
from repro.dist.partition import (LM_RULES, batch_shardings, cache_shardings,
                                  param_logical_axes, param_shardings,
                                  state_shardings)
from repro.dist.pipeline import pipeline_apply
from repro.dist.sharding import (Rules, _current_mesh, current_rules,
                                 grid_axes, resolve_spec, shard_constraint,
                                 use_rules)

__all__ = [
    "compressed_psum", "compressed_psum_scatter", "ring_allgather_matmul",
    "sync_grads", "wire_bytes",
    "DistGraph", "build_dist_graph", "distributed_spmm", "comm_volume",
    "Graph2D", "partition_2d", "distributed_spmm_2d", "scores_to_dense",
    "comm_volume_2d",
    "make_grid_mesh", "make_local_mesh", "make_production_mesh",
    "make_data_mesh", "replicated_sharding", "leading_axis_sharding",
    "LM_RULES", "batch_shardings", "cache_shardings", "param_logical_axes",
    "param_shardings", "state_shardings",
    "pipeline_apply",
    "Rules", "current_rules", "grid_axes", "resolve_spec",
    "shard_constraint", "use_rules", "_current_mesh",
]
