import numpy as np
import pytest

from bench.graphs import generate, rmat, stream_order

RMAT = dict(generator="rmat", scale=12, edgefactor=16, a=0.57, b=0.19,
            c=0.19, edges=52_000, vertices=3_500)


def test_same_seed_same_stream_other_seed_other_stream(config=RMAT):
    big = 2**31 + 12345
    a = stream_order(generate(config, big), "shuffled", big)
    b = stream_order(generate(config, big), "shuffled", big)
    c = stream_order(generate(config, big + 1), "shuffled", big + 1)
    assert a.dtype == np.uint32 and np.array_equal(a, b)
    assert not np.array_equal(a, c)


def test_every_seed_has_the_same_sizes(config=RMAT):
    sizes = {(len(e), int(e.max()) + 1)
             for e in (generate(config, s) for s in (1, 2, 3, 2**31 + 7))}
    assert len(sizes) == 1


def test_rmat_quadrant_shares():
    """Top level of the raw samples: the source falls in the lower half
    with probability c + d, the destination with b + d, both with d."""
    n = 400_000
    keys = rmat._sample(10, n, 0.57, 0.19, 0.19, np.random.SeedSequence(3))
    src, dst = keys >> np.uint64(10), keys & np.uint64(1023)
    top = np.uint64(512)
    loops = 1 - len(keys) / n     # self-loops were dropped from the sample
    assert 0 < loops < 0.05
    down, right = (src >= top), (dst >= top)
    assert abs(down.mean() - 0.24) < 0.01
    assert abs(right.mean() - 0.24) < 0.01
    assert abs((down & right).mean() - 0.05) < 0.01


def test_rmat_edges_distinct_without_loops_and_relabelled():
    e = generate(RMAT, 9)
    assert len(e) == RMAT["edges"] and int(e.max()) + 1 == RMAT["vertices"]
    assert not np.any(e[:, 0] == e[:, 1])
    keys = e[:, 0].astype(np.int64) << 32 | e[:, 1]
    assert len(np.unique(keys)) == len(e)
    # labels are random: a vertex's degree does not fall with its id, as
    # it does with R-MAT's raw ids, where the low ones hold the hubs
    def id_degree_corr(ids, n):
        deg = np.bincount(ids.astype(np.int64), minlength=n)
        touched = np.flatnonzero(deg)
        return np.corrcoef(touched, np.log(deg[touched]))[0, 1]
    assert abs(id_degree_corr(e.reshape(-1), RMAT["vertices"])) < 0.05
    raw = rmat._sample(12, 200_000, 0.57, 0.19, 0.19,
                       np.random.SeedSequence(9))
    assert id_degree_corr(raw >> np.uint64(12), 4096) < -0.3


def test_shuffled_order_is_a_permutation():
    e = generate(RMAT, 4)
    s = stream_order(e, "shuffled", 4)
    def key(x):
        return np.sort(x[:, 0].astype(np.int64) << 32 | x[:, 1])
    assert np.array_equal(key(e), key(s)) and not np.array_equal(e, s)
    with pytest.raises(ValueError):
        stream_order(e, "sorted", 4)
