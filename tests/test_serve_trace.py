"""Spans and counters of the serve loop (repro.trace, DESIGN.md §7): the
stages each chunk leaves, the ``rounds`` and ``carry_bytes`` counters
(in ``stats()`` and as marks in a profile), and the on-demand
``frontier_nnz`` gauge, on a directed chain, where a search from vertex
s runs exactly n - s rounds."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import trace
from repro.core import engine
from repro.datalog import datasets, programs
from repro.serve import ContinuousServer

N = 12
SOURCES = (2, 5, 9)
CHUNK = 2
STAGES = ("serve.admit", "pool.scan", "pool.upload", "pool.run",
          "pool.download", "serve.harvest")


def _chain():
    return datasets.Graph(N, np.stack([np.arange(N - 1),
                                       np.arange(1, N)], axis=1))


def _register(cs, semiring):
    g = _chain()
    if semiring == "bool":
        db = engine.Database(programs.bm(a=0).original.schema, {"id": N},
                             {"E": g.sparse_adjacency(),
                              "V": jnp.ones((N,), bool)})
        return cs.register("f", lambda a: programs.bm(a=a).optimized, db)
    mk = lambda a: programs.sssp(a=a, wmax=2, dmax=2 * N).optimized  # noqa
    db = engine.Database(programs.sssp(a=0, wmax=2, dmax=2 * N)
                         .original.schema, {"id": N, "w": 2, "d": 2 * N}, {})
    return cs.register("f", mk, db,
                       edges=g.sparse_adjacency(semiring="trop"))


def _serve(cs):
    reqs = [cs.submit("f", s) for s in SOURCES]
    cs.run_until_idle()
    return reqs


@pytest.mark.parametrize("semiring,itemsize", [("bool", 1), ("trop", 4)])
def test_chunk_spans_rounds_and_carry_bytes(semiring, itemsize):
    cs = ContinuousServer(max_batch=4, chunk_iters=CHUNK, warm_answers=0,
                          host_kernels=False)
    _register(cs, semiring)
    with trace.recording() as rec:
        reqs = _serve(cs)
    stats = cs.stats()
    assert [r.iters for r in reqs] == [N - s for s in SOURCES]
    assert stats["rounds"] == N - min(SOURCES)
    chunks = stats["chunks"]
    assert chunks == -(-(N - min(SOURCES)) // CHUNK)
    b = stats["families"]["f"]["pool_b"]
    assert stats["carry_bytes"] == chunks * (2 * 2 * b * N * itemsize
                                             + 2 * 4 * b)
    stages = [s for s in rec if s[0].startswith(("serve.", "pool."))]
    assert [n for n, _, _ in stages] == list(STAGES) * chunks
    ends = [t for _, t0, t1 in stages for t in (t0, t1)]
    assert ends == sorted(ends)

    # nothing is recorded outside a recording
    length = len(rec)
    _serve(cs)
    assert len(rec) == length
    with trace.recording() as fresh:
        pass
    assert fresh == []


@pytest.mark.parametrize("semiring", ["bool", "trop"])
def test_counters_and_spans_reach_the_profile(semiring, tmp_path):
    cs = ContinuousServer(max_batch=4, chunk_iters=CHUNK, warm_answers=0,
                          host_kernels=False)
    _register(cs, semiring)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(cs)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = [e for p in jax.profiler.ProfileData.from_file(str(path)).planes
              if p.name.startswith("/host:") for ln in p.lines
              for e in ln.events]
    marks = [dict(e.stats) for e in events if e.name == "counters"]
    stats = cs.stats()
    assert len(marks) == stats["chunks"] > 0
    for key in ("rounds", "carry_bytes"):
        assert sum(m[key] for m in marks) == stats[key] > 0
    assert set(STAGES) <= {e.name for e in events}


@pytest.mark.parametrize("semiring", ["bool", "trop"])
def test_frontier_nnz_read_on_demand(semiring):
    cs = ContinuousServer(max_batch=4, chunk_iters=CHUNK, warm_answers=0,
                          host_kernels=False)
    _register(cs, semiring)
    for s in SOURCES:
        cs.submit("f", s)
    assert cs.stats()["families"]["f"]["frontier_nnz"] == 0  # no pool yet
    cs.step()
    # each live lane's Δ is one vertex of the chain
    live = sum(N - s > CHUNK for s in SOURCES)
    assert cs.stats()["families"]["f"]["frontier_nnz"] == live
    cs.run_until_idle()
    assert cs.stats()["families"]["f"]["frontier_nnz"] == 0


@pytest.mark.parametrize("semiring", ["bool", "trop"])
def test_host_stepper_counts_rounds_and_no_bytes(semiring):
    cs = ContinuousServer(max_batch=4, chunk_iters=CHUNK, warm_answers=0,
                          host_kernels=True)
    _register(cs, semiring)
    with trace.recording() as rec:
        reqs = _serve(cs)
    stats = cs.stats()
    assert stats["rounds"] == max(r.iters for r in reqs) > 0
    assert stats["carry_bytes"] == 0
    assert {n for n, _, _ in rec} >= {"serve.admit", "serve.harvest"}
    assert not any(n.startswith("pool.") for n, _, _ in rec)
