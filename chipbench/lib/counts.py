"""Operations and compulsory bytes the algorithms need, from shapes alone.

Counted from the graph's stored entries and the layer widths, never from a
plan's padded layout, so every plan of the program is judged on the same
work. A multiply-add is two operations. Elementwise work (bias, relu,
softmax, the optimizer) is left out: it is a small share and not what the
matrix units do.
"""
from __future__ import annotations

F32 = 4
INDEX = 4


def gcn_epoch_flops(n: int, nnz: int, f: int, hidden: int, classes: int) -> float:
    """One full-batch epoch (forward and backward) of the two-layer GCN that
    projects before it aggregates, over ``Â`` with ``nnz`` stored entries.

    Layer 1: ``X W1`` forward and ``dW1`` (no gradient for X), ``Â`` and
    ``Âᵀ`` at width ``hidden``. Layer 2: ``H W2`` forward, ``dW2`` and
    ``dH``, ``Â`` and ``Âᵀ`` at width ``classes``."""
    return (4.0 * n * f * hidden + 4.0 * nnz * hidden
            + 6.0 * n * hidden * classes + 4.0 * nnz * classes)


def spmm_compulsory(n_rows: int, n_cols: int, nnz: int, k: int
                    ) -> tuple[float, float]:
    """(operations, bytes) of one ``(n_rows x n_cols) @ (n_cols x k)`` SpMM
    that reads each stored entry (index and value) and the row pointers
    once, the dense operand once, and writes the output once."""
    flops = 2.0 * nnz * k
    nbytes = (nnz * (INDEX + F32) + (n_rows + 1) * INDEX
              + n_cols * k * F32 + n_rows * k * F32)
    return flops, nbytes


def gcn_epoch_spmm_calls(n: int, nnz: int, hidden: int, classes: int
                         ) -> list[tuple[float, float]]:
    """The four aggregations of one GCN epoch: ``Â`` at widths ``hidden``
    and ``classes`` forward, ``Âᵀ`` at both widths backward."""
    return [spmm_compulsory(n, n, nnz, k)
            for k in (hidden, classes, classes, hidden)]


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The larger of compute time at the bf16 peak and traffic time at the
    HBM peak: the least time the chip could take for the work."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def sage_step_flops(layers: list[tuple[int, int]], dims: list[int]) -> float:
    """One sampled GraphSAGE-mean step (forward and backward) on one shard.

    ``layers`` holds, per block in the order the model applies them, the
    real destination rows and real sampled edges. Per block: the mean
    aggregation (2·edges·K_in, and again backward unless the input is the
    feature matrix), the two projections ``h_self W_self`` and
    ``mean W_neigh`` (forward, weight gradients, and input gradients
    unless the input is the feature matrix)."""
    total = 0.0
    for i, (rows, edges) in enumerate(layers):
        k_in, k_out = dims[i], dims[i + 1]
        grad_in = i > 0
        total += 2.0 * edges * k_in * (2 if grad_in else 1)
        total += 2.0 * 2 * rows * k_in * k_out * (3 if grad_in else 2)
    return total
