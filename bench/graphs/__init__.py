"""Seeded graph generators of the benchmark.

``generate(config, seed)`` returns the configuration's edge set as a
``(E, 2)`` uint32 array in a canonical order; ``stream_order`` then puts
it into the order a traffic file names.  Each generator is the file
``bench/graphs/<generator>.py`` named by the configuration's
``generator`` key, and reads only its own keys; each order is the file
``bench/orders/<order>.py`` (see ``bench/registry.py``).
"""
from __future__ import annotations

import numpy as np

from .. import registry


def _streams(seed: int) -> tuple[np.random.SeedSequence, ...]:
    """Independent child seeds: one for the edge set, one for the order."""
    return tuple(np.random.SeedSequence(int(seed)).spawn(2))


def generate(config: dict, seed: int) -> np.ndarray:
    gen = registry.plugin("graphs", config["generator"])
    return gen.edges(config, _streams(seed)[0])


def stream_order(edges: np.ndarray, order: str, seed: int) -> np.ndarray:
    """The edge stream as the partitioner reads it."""
    return registry.plugin("orders", order).order(edges, _streams(seed)[1])
