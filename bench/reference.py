"""Plain references, independent of the program under test.

They read only the benchmark's own generated edge list: no relation,
plan or answer the program built.  ``csr`` symmetrises the edge list as
Graph500 treats the graph (undirected), coalesces duplicate edges (the
least weight wins, as min-plus does) and keeps self-loops, which change
no reachability and no shortest distance.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp


@dataclasses.dataclass
class Csr:
    n: int
    indptr: np.ndarray
    nbr: np.ndarray
    w: np.ndarray | None

    @property
    def nnz(self) -> int:
        return len(self.nbr)

    def scipy(self) -> sp.csr_matrix:
        data = self.w if self.w is not None else np.ones(self.nnz)
        return sp.csr_matrix((np.asarray(data, np.float64), self.nbr,
                              self.indptr), shape=(self.n, self.n))


def csr(n: int, edges: np.ndarray, weights: np.ndarray | None) -> Csr:
    """The symmetrised, coalesced adjacency of a generated edge list."""
    src = np.concatenate([edges[:, 0], edges[:, 1]]).astype(np.int64)
    dst = np.concatenate([edges[:, 1], edges[:, 0]]).astype(np.int64)
    key = src * n + dst
    order = np.argsort(key, kind="stable")
    key = key[order]
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    w = None
    if weights is not None:
        ww = np.concatenate([weights, weights])[order]
        w = np.minimum.reduceat(ww, first)
    key = key[first]
    s, d = key // n, key % n
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(s, minlength=n), out=indptr[1:])
    return Csr(n, indptr, d, w)


def _out_edges(indptr, front):
    """Positions of the out-edges of ``front`` in CSR order."""
    lo, cnt = indptr[front], indptr[front + 1] - indptr[front]
    starts = np.cumsum(cnt) - cnt
    return np.repeat(lo - starts, cnt) + np.arange(int(cnt.sum()))


def bfs_levels(g: Csr, a: int) -> np.ndarray:
    """Hop distance of every vertex from ``a`` (-1: unreached), by a
    level-synchronous BFS."""
    level = np.full(g.n, -1, np.int64)
    level[a] = 0
    front = np.array([a], np.int64)
    k = 0
    while len(front):
        k += 1
        mark = np.zeros(g.n, bool)
        mark[g.nbr[_out_edges(g.indptr, front)]] = True
        front = np.flatnonzero(mark & (level < 0))
        level[front] = k
    return level


def shortest_paths(g: Csr, sources) -> np.ndarray:
    """``(len(sources), n)`` float64 shortest distances (Dijkstra over the
    real weights; ``inf`` where unreached)."""
    from scipy.sparse.csgraph import dijkstra
    return dijkstra(g.scipy(), directed=True, indices=np.asarray(sources))
