"""Seeded graph generators of the benchmark.

``generate(config, seed)`` returns the configuration's edge set as a
``(E, 2)`` uint32 array in a canonical order; ``stream_order`` then puts
it into the order a traffic file names.  Each generator is chosen by the
configuration's ``generator`` key and reads only its own keys.
"""
from __future__ import annotations

import numpy as np

from .rmat import rmat_edges

GENERATORS = {"rmat": rmat_edges}
ORDERS = ("shuffled",)


def _streams(seed: int) -> tuple[np.random.SeedSequence, ...]:
    """Independent child seeds: one for the edge set, one for the order."""
    return tuple(np.random.SeedSequence(int(seed)).spawn(2))


def generate(config: dict, seed: int) -> np.ndarray:
    kind = config["generator"]
    if kind not in GENERATORS:
        raise ValueError(f"unknown generator {kind!r}; known: "
                         f"{sorted(GENERATORS)}")
    return GENERATORS[kind](config, _streams(seed)[0])


def stream_order(edges: np.ndarray, order: str, seed: int) -> np.ndarray:
    """The edge stream as the partitioner reads it."""
    if order == "shuffled":
        rng = np.random.default_rng(_streams(seed)[1])
        return np.ascontiguousarray(edges[rng.permutation(len(edges))])
    raise ValueError(f"unknown stream order {order!r}; known: {ORDERS}")
