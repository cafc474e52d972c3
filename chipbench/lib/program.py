"""The benchmark's data, in the program's own input types.

The only module of the yardstick that imports the program: it hands the
generated graph to the system under test as a ``repro`` ``GraphDataset``.
"""
from __future__ import annotations

import numpy as np


def graph_dataset(name: str, g: dict, x):
    """GraphDataset over the edges ``src -> dst`` of ``g``, padded as the
    program's generator pads (stored entries to a multiple of 1024)."""
    import jax.numpy as jnp

    from repro.core import sparse as sp
    from repro.data.graphs import GraphDataset

    n, _, classes = (int(v) for v in g["meta"])
    src, dst = g["src"], g["dst"]
    pad = lambda k: -(-k // 1024) * 1024   # noqa: E731
    loops = np.arange(n, dtype=np.int32)
    coo = sp.coo_from_edges(src, dst, None, n, n, pad_to=pad(len(src)))
    coo_sl = sp.coo_from_edges(np.concatenate([src, loops]),
                               np.concatenate([dst, loops]), None, n, n,
                               pad_to=pad(len(src) + n))
    split = g["split"]
    return GraphDataset(
        name=name, coo=coo, coo_sl=coo_sl, x=x, y=jnp.asarray(g["y"]),
        train_mask=jnp.asarray(split == 0), val_mask=jnp.asarray(split == 1),
        test_mask=jnp.asarray(split == 2), num_classes=classes)
