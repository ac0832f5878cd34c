"""The plain references against Floyd–Warshall at a tiny size, and the
controls against the references."""

import numpy as np
import pytest

import harness
import reference as ref


def _graph(seed, n=40, m=90):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, (m, 2)).astype(np.int32)
    w = rng.random(m).astype(np.float32)
    w[0] = 0.0                      # a zero weight is an edge
    return n, e, w


def _floyd_warshall(n, e, w):
    d = np.full((n, n), np.inf)
    np.fill_diagonal(d, 0.0)
    for (a, b), x in zip(e, w.astype(np.float64)):
        for u, v in ((a, b), (b, a)):      # undirected, least weight wins
            d[u, v] = min(d[u, v], x)
    for k in range(n):
        d = np.minimum(d, d[:, k:k + 1] + d[k:k + 1, :])
    return d


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_references_equal_floyd_warshall(seed):
    n, e, w = _graph(seed)
    fw = _floyd_warshall(n, e, w)
    reach = harness.load_module("families", "reach")
    sssp = harness.load_module("families", "sssp")
    g = ref.csr(n, e, w)
    sources = list(range(n))
    for s, got in zip(sources, reach.reference(ref.csr(n, e, None), sources)):
        assert np.array_equal(got, np.isfinite(fw[s]))
    for s, got in zip(sources, sssp.reference(g, sources)):
        np.testing.assert_allclose(got, fw[s], rtol=1e-12)


def test_csr_coalesces_and_keeps_the_least_weight():
    e = np.array([[0, 1], [1, 0], [0, 1], [2, 2]], np.int32)
    w = np.array([0.5, 0.25, 0.75, 0.1], np.float32)
    g = ref.csr(3, e, w)
    assert g.nnz == 3                           # 0-1, 1-0, 2-2
    assert list(g.nbr) == [1, 0, 2]
    assert list(g.w) == [0.25, 0.25, np.float32(0.1)]


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_controls_fail_the_limits(seed):
    """The controls fail the configurations' limits: the BFS that stops
    a level short, and shortest paths in bfloat16."""
    from graphs import graph500
    params = {"scale": 10, "edgefactor": 16, "A": 0.57, "B": 0.19,
              "C": 0.19, "weights": "uniform01"}
    n, e, w, keys = graph500.generate(dict(params, graph_seed=seed), 1)
    keys = keys[:4]
    for fam_name, limit in (("reach", 0.0), ("sssp", 1e-4)):
        fam = harness.load_module("families", fam_name)
        g = ref.csr(n, e, w if fam_name == "sssp" else None)
        want = fam.reference(g, keys)
        (num,) = fam.compare(fam.control(g, keys), want).values()
        (same,) = fam.compare(want, want).values()
        assert same == 0.0 and num > limit
