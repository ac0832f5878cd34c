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
from repro.core import semiring as sr_mod
from repro.datalog import datasets, programs
from repro.serve import ContinuousServer, slots

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
    # the carry stays on the device: each chunk brings back the live
    # mask (1 byte a lane) and the rounds (4); the three rows go up in
    # one splice with its index (4 bytes a lane), and each comes back in
    # the chunk that saw it converge (three chunks), with its index; a
    # 𝔹 row crosses bit-packed
    row = -(-N // 8) if semiring == "bool" else N * itemsize
    assert stats["rows_in"] == stats["rows_out"] == len(SOURCES)
    assert stats["carry_bytes"] == (chunks * 5 * b + 4 * b
                                    + len(SOURCES) * row
                                    + len(SOURCES) * (4 * b + row))
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
    for key in ("rounds", "carry_bytes", "rows_in", "rows_out"):
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
def test_pool_programs_compile_once_across_admission_counts(semiring):
    """Splice, live mask and copy-out each compile once for a pool's
    shape, whether a chunk admits or copies out 1 row or all B; every
    row comes out as the single-source run gives it."""
    from repro.sparse import fixpoint as fx

    n, b = 2 * N + 1, 4              # a shape no other test compiles
    coords = np.stack([np.arange(n - 1), np.arange(1, n)], axis=1)
    rel = datasets.Graph(n, coords).sparse_adjacency(semiring=semiring)
    st = slots.JaxChunkStepper(rel, n, b, fx.CompiledChunk(CHUNK))
    progs = (slots._splice, slots._live, slots._gather)
    sizes = [p._cache_size() for p in progs]
    sr = sr_mod.get(semiring, lib="np")
    for k in range(1, b + 1):
        inits = {}
        for j in range(k):
            inits[j] = np.full(n, sr.zero, sr.dtype)
            inits[j][3 * j + k] = sr.one
            st.admit(j, inits[j])
        while st.live_lanes()[:k].any():
            st.step(CHUNK)
        for j, init in inits.items():
            y, iters = st.extract(j)
            st.release(j)
            want, want_iters = fx.fixpoint(rel, init)
            assert np.array_equal(y, np.asarray(want)) and y.flags.writeable
            assert iters == int(want_iters)
    assert [p._cache_size() for p in progs] == [m + 1 for m in sizes]
    assert st.rows_in == st.rows_out == b * (b + 1) // 2


@pytest.mark.parametrize("semiring", ["bool", "trop"])
def test_no_full_carry_reaches_the_host(semiring, monkeypatch):
    """The (B, n) carry stays on the device: what a chunk brings back is
    the live mask, the rounds and converged rows, never a (B, n) array."""
    from jax._src import array as jax_array

    fetched = []
    value = jax_array.ArrayImpl._value

    def counting(self):
        fetched.append(self.shape)
        return value.fget(self)

    monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(counting))
    cs = ContinuousServer(max_batch=4, chunk_iters=CHUNK, warm_answers=0,
                          host_kernels=False)
    _register(cs, semiring)
    reqs = _serve(cs)
    assert [r.iters for r in reqs] == [N - s for s in SOURCES]
    b = cs.stats()["families"]["f"]["pool_b"]
    assert fetched and all(np.prod(s) < b * N for s in fetched), fetched
    stepper = cs._families["f"].pool.stepper
    assert isinstance(stepper.y, jax.Array)
    assert isinstance(stepper.d, jax.Array)


@pytest.mark.parametrize("semiring", ["bool", "trop"])
def test_host_stepper_counts_rounds_and_no_bytes(semiring):
    cs = ContinuousServer(max_batch=4, chunk_iters=CHUNK, warm_answers=0,
                          host_kernels=True)
    _register(cs, semiring)
    with trace.recording() as rec:
        reqs = _serve(cs)
    stats = cs.stats()
    assert stats["rounds"] == max(r.iters for r in reqs) > 0
    assert stats["carry_bytes"] == stats["rows_in"] == stats["rows_out"] == 0
    assert {n for n, _, _ in rec} >= {"serve.admit", "serve.harvest"}
    assert not any(n.startswith("pool.") for n, _, _ in rec)
