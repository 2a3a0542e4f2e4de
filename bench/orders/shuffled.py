"""The edge stream in random order: a permutation drawn from the seed."""
import numpy as np


def order(edges: np.ndarray, seed_seq: np.random.SeedSequence) -> np.ndarray:
    rng = np.random.default_rng(seed_seq)
    return np.ascontiguousarray(edges[rng.permutation(len(edges))])
