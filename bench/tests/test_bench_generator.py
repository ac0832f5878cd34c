"""The Graph500 generator: deterministic per seed, the stated sizes, and
the same graph under other names for every seed."""

import numpy as np

import reference as ref
from graphs import graph500

PARAMS = {"scale": 10, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19,
          "weights": "uniform01", "graph_seed": 0}


def test_same_seed_same_graph_and_large_seeds():
    seed = 2**31 + 12345          # past 32 signed bits, as the driver's are
    n1, e1, w1, k1 = graph500.generate(PARAMS, seed)
    n2, e2, w2, k2 = graph500.generate(PARAMS, seed)
    assert n1 == n2 == 1024
    assert np.array_equal(e1, e2) and np.array_equal(w1, w2)
    assert np.array_equal(k1, k2)
    _, e3, _, _ = graph500.generate(PARAMS, seed + 2**32)
    assert not np.array_equal(e1, e3)


def test_stated_sizes_at_scale_10():
    n, e, w, keys = graph500.generate(PARAMS, 3)
    assert e.shape == (16 * 1024, 2) and e.dtype == np.int32
    assert e.min() >= 0 and e.max() < n
    assert w.shape == (16 * 1024,) and w.dtype == np.float32
    assert w.min() >= 0.0 and w.max() < 1.0
    loops = e[:, 0] == e[:, 1]
    linked = np.flatnonzero(np.bincount(e[~loops].ravel(), minlength=n))
    assert np.array_equal(np.sort(keys), linked)
    _, _, none, _ = graph500.generate(dict(PARAMS, weights=None), 3)
    assert none is None


def test_seeds_rename_one_graph():
    """Two seeds give the same graph under other names, and ``keys[k]``
    is the same vertex in both: its degree and its distances agree."""
    n, e1, w1, k1 = graph500.generate(PARAMS, 5)
    _, e2, w2, k2 = graph500.generate(PARAMS, 2**33 + 6)
    assert not np.array_equal(e1, e2) and np.array_equal(w1, w2)
    g1, g2 = ref.csr(n, e1, w1), ref.csr(n, e2, w2)
    assert g1.nnz == g2.nnz
    deg1, deg2 = np.diff(g1.indptr), np.diff(g2.indptr)
    assert np.array_equal(deg1[k1], deg2[k2])
    d1 = ref.shortest_paths(g1, k1[:3])
    d2 = ref.shortest_paths(g2, k2[:3])
    assert np.array_equal(np.sort(d1, axis=1), np.sort(d2, axis=1))
    _, e3, _, _ = graph500.generate(dict(PARAMS, graph_seed=1), 5)
    assert ref.csr(n, e3, None).nnz != g1.nnz


def test_skew_follows_the_initiator():
    """Quadrant A (both bits 0) is chosen with probability 0.57 per level,
    so degrees are heavily skewed: the top 1% of vertices hold far more
    than 1% of the edge ends."""
    n, e, _, _ = graph500.generate(PARAMS, 4)
    deg = np.bincount(e.ravel(), minlength=n)
    top = np.sort(deg)[::-1][: n // 100].sum()
    assert top > 0.1 * deg.sum()
