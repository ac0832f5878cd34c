"""Single-source shortest paths (Graph500 SSSP kernel) as the ``sssp``
family: ``programs.sssp(a).optimized`` over the tropical semiring, with
the real-weighted adjacency given as ``edges=``.

The program adds float32 weights along each path; the reference is
Dijkstra in float64 over the same float32 weights.  The compared number
is the widest gap between the two, relative to the distance (at least
1), with a vertex that one side reaches and the other does not counted
as an infinite gap.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

import reference as ref

SEMIRING = "trop"
#: a float32 weight per edge, a float32 distance per (vertex, lane)
EDGE_VALUE_BYTES = 4
LANE_BYTES = 4


def register(server, rel, n: int, program: dict):
    """Register the family the way users do (as ``chip_smoke.py`` does)."""
    from repro.core import engine
    from repro.datalog import programs

    wmax, dmax = int(program["wmax"]), int(program["dmax"])
    db = engine.Database(
        programs.sssp(a=0, wmax=wmax, dmax=dmax).original.schema,
        {"id": n, "w": wmax, "d": dmax}, {})
    return server.register(
        "sssp", lambda a: programs.sssp(a=a, wmax=wmax, dmax=dmax).optimized,
        db, edges=rel)


def reference(g: ref.Csr, sources) -> list[np.ndarray]:
    return list(ref.shortest_paths(g, sources))


def _bf16(x):
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def control(g: ref.Csr, sources) -> list[np.ndarray]:
    """The reference computed in bfloat16, the precision below the
    configuration's float32: weights and every tentative distance are
    rounded to bfloat16 (label-correcting search to its fixpoint)."""
    w = _bf16(g.w)
    out = []
    for s in sources:
        dist = np.full(g.n, np.inf, np.float32)
        dist[int(s)] = 0.0
        front = np.array([int(s)], np.int64)
        while len(front):
            e = ref._out_edges(g.indptr, front)
            src = np.repeat(front, np.diff(g.indptr)[front])
            cand = _bf16(dist[src] + w[e])
            before = dist.copy()
            np.minimum.at(dist, g.nbr[e], cand)
            front = np.flatnonzero(dist < before)
        out.append(dist.astype(np.float64))
    return out


def compare(got: list, want: list) -> dict:
    gap = 0.0
    for a, b in zip(got, want):
        a = np.asarray(a, np.float64)
        fa, fb = np.isfinite(a), np.isfinite(b)
        if not np.array_equal(fa, fb):
            return {"dist_gap": float("inf")}
        d = np.abs(a[fa] - b[fb]) / np.maximum(1.0, np.abs(b[fb]))
        gap = max(gap, float(d.max(initial=0.0)))
    return {"dist_gap": gap}
