"""Check the blocks the device sampler drew against the graph itself.

For each block (applied innermost-first, as the model folds them) and each
destination row, with ``S`` the sampled neighbours of destination ``d``:

* every edge ``s -> d`` of ``S`` is an edge of the graph, once;
* ``|S| = min(in-degree(d), fanout)`` (sampling without replacement keeps
  every edge of a node whose degree is at most the fanout);
* each sampled edge carries the graph's value 1.0, each empty slot 0;
* the destination's own row among the sources holds ``d`` (self term);
* rows of pad destinations are empty; the real source ids are distinct.

It returns the count of rows or edges that break one of these, and the
reference's own view of each block (see ``chipbench.reference.sage``).
"""
from __future__ import annotations

import numpy as np


class EdgeIndex:
    """Sorted ``dst * n + src`` keys and in-degrees of the benchmark's graph."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int):
        self.n = n
        self.keys = np.sort(dst.astype(np.int64) * n + src.astype(np.int64))
        self.deg = np.bincount(dst, minlength=n)

    def has(self, dst: np.ndarray, src: np.ndarray) -> np.ndarray:
        k = dst.astype(np.int64) * self.n + src.astype(np.int64)
        pos = np.clip(np.searchsorted(self.keys, k), 0, len(self.keys) - 1)
        return self.keys[pos] == k


def check_blocks(blocks: list[dict], seeds: np.ndarray, n_real: int,
                 fanouts, graph: EdgeIndex) -> tuple[int, list[dict]]:
    """``blocks``: innermost-first dicts of numpy arrays ``src_ids``,
    ``dst_pos``, ``row``, ``col``, ``val`` (the sampler's packed layout:
    ``row`` the destination position of each slot, ``col`` its source
    position, ``n_src`` for an empty slot). ``fanouts`` outermost-last, as
    the program takes them. Returns (faults, reference views)."""
    n = graph.n
    faults = 0
    dst = np.where(np.arange(len(seeds)) < n_real, seeds, n).astype(np.int64)
    views = [None] * len(blocks)
    for i in range(len(blocks) - 1, -1, -1):
        b = blocks[i]
        src_ids = np.asarray(b["src_ids"], np.int64)
        n_src, n_dst = len(src_ids), len(dst)
        row = np.asarray(b["row"], np.int64)
        col = np.asarray(b["col"], np.int64)
        val = np.asarray(b["val"])
        dst_pos = np.asarray(b["dst_pos"], np.int64)
        real_src = src_ids[src_ids < n]
        faults += int(len(real_src) - len(np.unique(real_src)))
        faults += int(np.sum(src_ids > n))
        if len(dst_pos) != n_dst or row.min(initial=0) < 0 or \
                row.max(initial=0) >= n_dst:
            return faults + n_dst, views
        valid = col < n_src
        faults += int(np.sum(valid & (val != 1.0)))
        faults += int(np.sum(~valid & (val != 0.0)))
        r, c = row[valid], col[valid]
        s, d = src_ids[c], dst[r]
        bad_edge = (s >= n) | (d >= n)
        ok_edge = ~bad_edge
        ok_edge[ok_edge] = graph.has(d[ok_edge], s[ok_edge])
        faults += int(np.sum(~ok_edge))
        keys = d * (n + 1) + s
        faults += int(len(keys) - len(np.unique(keys)))
        count = np.bincount(r, minlength=n_dst)
        fan = fanouts[i]
        want = np.where(dst < n, np.minimum(graph.deg[np.minimum(dst, n - 1)],
                                            fan if fan is not None else n), 0)
        faults += int(np.sum(count != want))
        real = dst < n
        sp = np.clip(dst_pos, 0, n_src - 1)
        faults += int(np.sum(real & ((dst_pos >= n_src) | (src_ids[sp] != dst))))
        faults += int(np.sum(~real & (dst_pos < n_src)))
        views[i] = {"src_ids": src_ids.astype(np.int32),
                    "self_pos": np.where(real, dst_pos, n_src).astype(np.int32),
                    "nbr_row": np.where(valid, row, n_dst).astype(np.int32),
                    "nbr_col": np.where(valid, col, n_src).astype(np.int32)}
        dst = np.where(src_ids < n, src_ids, n)
    return faults, views
