"""The benchmark's own graph generator: Table-1-shaped R-MAT graphs.

A copy of the R-MAT generator in ``src/repro/data/graphs.py`` (the yardstick
must not move when the program's generator changes), with the features made
on the device in one jitted call. Everything is a function of
``(dataset, graph_seed)`` from the traffic file; ``--seed`` never changes the
graph, so every seed of a cell does the same amount of work.

The host part (edges, labels, split) is cached on disk inside the checkout at
``.chipbench_cache/<dataset>-g<graph_seed>.npz``, so only the first run of a
checkout pays the ~15 s of generation.
"""
from __future__ import annotations

import os

import numpy as np

# Table 1 of the paper (nodes, edges before R-MAT dedup, features, classes).
TABLE1 = {
    "reddit": (232_965, 11_606_919, 602, 41),
    "ogbn-products": (2_449_029, 61_859_140, 100, 47),
    "ogbn-proteins": (132_534, 39_561_252, 8, 112),
}

CACHE_DIR = ".chipbench_cache"


def rmat_edges(n: int, m: int, seed: int,
               probs=(0.57, 0.19, 0.19, 0.05)) -> tuple[np.ndarray, np.ndarray]:
    """Vectorised R-MAT over ceil(log2 n) bit levels; duplicate edges are
    dropped, so the edge count ends a few % under ``m``."""
    rng = np.random.default_rng(seed)
    levels = max(int(np.ceil(np.log2(max(n, 2)))), 1)
    a, b, c, _ = probs
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for _ in range(levels):
        r = rng.random(m)
        right = (r >= a) & (r < a + b)
        down = (r >= a + b) & (r < a + b + c)
        both = r >= a + b + c
        src = src * 2 + (down | both)
        dst = dst * 2 + (right | both)
    src %= n
    dst %= n
    _, keep = np.unique(src * n + dst, return_index=True)
    return src[keep].astype(np.int32), dst[keep].astype(np.int32)


def sizes(dataset: str, scale: float = 1.0) -> tuple[int, int, int, int]:
    """(nodes, R-MAT draws, features, classes) of ``dataset`` at ``scale``."""
    nodes, edges, feat, classes = TABLE1[dataset]
    n = max(int(nodes * scale), 64)
    return n, max(int(edges * scale), 4 * n), feat, classes


def host_graph(dataset: str, graph_seed: int, scale: float = 1.0,
               cache_root: str | None = None) -> dict:
    """Edges (``src -> dst``), labels and the 60/20/20 split, as numpy.

    Labels follow R-MAT's id-local communities (leading bits of the node id)
    with 10% noise, as the program's generator does, so training learns."""
    path = None
    if cache_root is not None:
        path = os.path.join(cache_root, CACHE_DIR,
                            f"{dataset}-s{scale:g}-g{graph_seed}.npz")
        if os.path.exists(path):
            with np.load(path) as z:
                return {k: z[k] for k in z.files}
    n, m, feat, classes = sizes(dataset, scale)
    src, dst = rmat_edges(n, m, seed=graph_seed)
    rng = np.random.default_rng(graph_seed + 1)
    comm = np.arange(n, dtype=np.int64) * classes // n
    noise = rng.integers(0, classes, n)
    y = np.where(rng.random(n) < 0.1, noise, comm).astype(np.int32)
    split = np.zeros(n, np.int8)            # 0 train, 1 val, 2 test
    perm = rng.permutation(n)
    split[perm[int(0.6 * n):int(0.8 * n)]] = 1
    split[perm[int(0.8 * n):]] = 2
    g = {"src": src, "dst": dst, "y": y, "split": split,
         "meta": np.array([n, feat, classes], np.int64)}
    if path is not None:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp.npz"
        np.savez(tmp, **g)
        os.replace(tmp, path)
    return g


def device_features(n: int, feat: int, y, graph_seed: int):
    """(n, feat) float32 features on the device: standard normal from the
    graph seed, plus 2.0 at column ``y % feat`` so they carry the label."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, y):
        x = jax.random.normal(key, (n, feat), jnp.float32)
        return x.at[jnp.arange(n), y % feat].add(2.0)

    return make(jax.random.PRNGKey(graph_seed), jnp.asarray(y))
