"""Operations and compulsory bytes of full-batch GAT training, from shapes
alone (the conventions of ``lib/counts.py``: a multiply-add is two
operations, elementwise work such as LeakyReLU, softmax and the optimizer
is left out, entries are the stored entries of ``A + I``, never a plan's
padded layout).

Per layer of ``H`` heads of ``F`` features (``K = H·F``) over ``n`` nodes
and ``nnz`` entries, one epoch (forward and backward) needs:

* the projection ``z = h W``: forward, ``dW`` and ``dh`` (no ``dh`` for the
  first layer, whose input is the features);
* the two node scores ``a_dstᵀ z`` and ``a_srcᵀ z``: forward, and the
  gradients in ``a`` and in ``z``;
* three passes over the entries at width ``K``: the multi-head SpMM, its
  transpose for ``dz``, and the SDDMM for the weights' gradient.
"""
from __future__ import annotations

from chipbench.lib.counts import F32, INDEX


def layer_dims(f: int, heads, head_dim, concat) -> list[tuple[int, int, int]]:
    """(input width, heads, head width) of each layer."""
    out, d_in = [], f
    for h, fd, cat in zip(heads, head_dim, concat):
        out.append((d_in, h, fd))
        d_in = h * fd if cat else fd
    return out


def gat_epoch_flops(n: int, nnz: int, dims) -> float:
    total = 0.0
    for i, (d_in, h, fd) in enumerate(dims):
        k = h * fd
        total += 2.0 * n * d_in * k * (2 if i == 0 else 3)
        total += 2 * 2.0 * n * k * 3            # two scores, fwd + 2 grads
        total += 3 * 2.0 * nnz * k              # SpMM, transpose, SDDMM
    return total


def pass_compulsory(n_rows: int, n_cols: int, nnz: int, heads: int,
                    k: int) -> tuple[float, float]:
    """(operations, bytes) of one pass over the stored entries at width
    ``k``, a multi-head SpMM or SDDMM alike: each entry's index and its
    ``heads`` values (read, or written by the SDDMM) once, the row
    pointers once, both dense operands once (SpMM: the gathered rows and
    the output; SDDMM: the gathered rows and the output gradient)."""
    flops = 2.0 * nnz * k
    nbytes = (nnz * (INDEX + heads * F32) + (n_rows + 1) * INDEX
              + n_cols * k * F32 + n_rows * k * F32)
    return flops, nbytes


def gat_epoch_spmm_calls(n: int, nnz: int, dims) -> list:
    """The multi-head SpMMs of one epoch: per layer, forward and the cached
    transpose's."""
    return [pass_compulsory(n, n, nnz, h, h * fd)
            for _, h, fd in dims for _ in range(2)]


def gat_epoch_sddmm_calls(n: int, nnz: int, dims) -> list:
    """The gather-SDDMMs of one epoch, one per layer."""
    return [pass_compulsory(n, n, nnz, h, h * fd) for _, h, fd in dims]
