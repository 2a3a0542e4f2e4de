"""Graph500 Kernel-1 style R-MAT edge sets.

Each of ``edgefactor * 2**scale`` edges descends ``scale`` levels of the
adjacency matrix, choosing a quadrant with probabilities a, b, c and
1 - a - b - c.  As the repository's ``rmat_graph`` does, self-loops and
duplicate edges are dropped.  As Graph500's generator does, the vertex
labels are then permuted at random, so a vertex's id says nothing of its
degree.  The samples are drawn in slices on a few threads (numpy
releases the interpreter lock in these loops), each slice from its own
child seed, so the edge set depends only on the seed.

So that every seed gives the same sizes, and with them the same compiled
programs, the configuration fixes |E| and |V|: a seeded random subset of
the distinct edges is left out to keep exactly ``edges`` of them, and the
touched vertices get distinct random labels in ``[0, vertices)``, the
largest of which is set to ``vertices - 1``.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

SLICES = 16
THREADS = 8


def _sample(scale: int, n: int, a: float, b: float, c: float,
            seed: np.random.SeedSequence) -> np.ndarray:
    """``n`` R-MAT samples as uint64 keys ``src << scale | dst``, without
    self-loops.  Quadrant per level: (0,0) below a, (0,1) below a+b,
    (1,0) below a+b+c, else (1,1); so ``down`` is r >= a+b and ``right``
    flips at each of the three thresholds."""
    rng = np.random.default_rng(seed)
    src = np.zeros(n, np.uint32)
    dst = np.zeros(n, np.uint32)
    a, ab, abc = (np.float32(x) for x in (a, a + b, a + b + c))
    for _ in range(scale):
        r = rng.random(n, dtype=np.float32)
        down = r >= ab
        right = (r >= a) ^ down ^ (r >= abc)
        src <<= np.uint32(1)
        src |= down
        dst <<= np.uint32(1)
        dst |= right
    keep = src != dst
    return ((src[keep].astype(np.uint64) << np.uint64(scale))
            | dst[keep].astype(np.uint64))


def rmat_edges(config: dict, seed: np.random.SeedSequence) -> np.ndarray:
    scale = int(config["scale"])
    n = int(config["edgefactor"]) << scale
    a, b, c = (float(config[x]) for x in ("a", "b", "c"))
    bounds = np.linspace(0, n, SLICES + 1).astype(np.int64)
    seeds = seed.spawn(SLICES)
    with ThreadPoolExecutor(THREADS) as pool:
        parts = list(pool.map(
            lambda i: _sample(scale, int(bounds[i + 1] - bounds[i]),
                              a, b, c, seeds[i]), range(SLICES)))
    keys = np.unique(np.concatenate(parts))       # sorted, no duplicates
    want, num_vertices = int(config["edges"]), int(config["vertices"])
    if len(keys) < want:
        raise ValueError(f"R-MAT sample has {len(keys)} distinct edges, "
                         f"fewer than the configuration's {want}")
    rng = np.random.default_rng(seed.spawn(1)[0])
    drop = rng.choice(len(keys), len(keys) - want, replace=False)
    keys = np.delete(keys, drop)
    mask = np.uint64((1 << scale) - 1)
    src = (keys >> np.uint64(scale)).astype(np.int64)
    dst = (keys & mask).astype(np.int64)
    touched = np.zeros(1 << scale, bool)
    touched[src] = True
    touched[dst] = True
    ids = np.flatnonzero(touched)
    if len(ids) > num_vertices:
        raise ValueError(f"R-MAT sample touches {len(ids)} vertices, "
                         f"more than the configuration's {num_vertices}")
    labels = rng.permutation(num_vertices)[:len(ids)]
    labels[np.argmax(labels)] = num_vertices - 1
    new_id = np.zeros(1 << scale, np.int64)
    new_id[ids] = labels
    edges = np.empty((len(keys), 2), np.uint32)
    edges[:, 0] = new_id[src]
    edges[:, 1] = new_id[dst]
    return edges
