"""Single-source reachability (Graph500 BFS kernel) as the ``reach``
family: ``programs.bm(a).optimized`` over the Boolean semiring.

The answer of a query is the set of vertices reachable from its source,
the source included.  It is exact, so the comparison is exact.
"""

from __future__ import annotations

import numpy as np

import reference as ref

SEMIRING = "bool"
#: bytes per edge value and per (vertex, lane) of the carry, as the
#: least any implementation stores them: no edge value, one bit a lane
EDGE_VALUE_BYTES = 0
LANE_BYTES = 1 / 8


def register(server, rel, n: int, program: dict):
    """Register the family the way users do (as ``chip_smoke.py`` does)."""
    import jax.numpy as jnp

    from repro.core import engine
    from repro.datalog import programs

    db = engine.Database(programs.bm(a=0).original.schema, {"id": n},
                         {"E": rel, "V": jnp.ones((n,), bool)})
    return server.register("reach", lambda a: programs.bm(a=a).optimized,
                           db)


def reference(g: ref.Csr, sources) -> list[np.ndarray]:
    return [ref.bfs_levels(g, int(s)) >= 0 for s in sources]


def control(g: ref.Csr, sources) -> list[np.ndarray]:
    """The reference with the guarantee of exact answers broken: each
    search stops one level short of its last."""
    out = []
    for s in sources:
        level = ref.bfs_levels(g, int(s))
        out.append((level >= 0) & (level < level.max()))
    return out


def compare(got: list, want: list) -> dict:
    """``mismatched``: answers that differ from the reference in any
    vertex."""
    bad = sum(not np.array_equal(np.asarray(a, bool), b)
              for a, b in zip(got, want))
    return {"mismatched": float(bad)}
