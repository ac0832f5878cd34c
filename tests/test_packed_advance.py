"""The 𝔹 chunk's packed pull round (``sparse/fixpoint.py``): lanes packed
32 to a uint32 word, a round gathers source words in destination order
and ORs each destination's run.  It must give bit for bit what the
scatter chunk it replaced gives, which stays reachable through
``advance=``, and what the host's packed round
(``coo_spmm.bool_round_packed``) gives; it must engage only for 𝔹 with
no ``advance=`` override and no active mesh, say so in
``packed_rounds``, and build the pull view once per operator."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core import semiring as sr_mod
from repro.datalog import datasets, programs
from repro.distributed import sharding as sh
from repro.kernels import coo_spmm
from repro.serve import ContinuousServer
from repro.sparse import SparseRelation, contract
from repro.sparse import fixpoint as fx

N = 40
BATCHES = (1, 2, 31, 33, 64)
CASES = ("padded", "dead_edge", "no_in_edges", "empty", "directed")
BOOL = sr_mod.get("bool")


def _operator(case: str, seed: int = 0):
    """``(rel, live)``: the operator under test and the same edges with
    any 0̄ edge dropped, as the host round assumes."""
    rng = np.random.default_rng(seed)
    if case == "empty":
        rel = SparseRelation.from_coo(np.zeros((0, 2)), np.zeros(0, bool),
                                      (N, N), "bool", capacity=4)
        return rel, rel
    e = rng.integers(0, N, (3 * N, 2))
    if case == "no_in_edges":
        e[:, 1] %= N // 2                 # vertices N/2.. have no in-edge
    if case == "directed":
        e = np.sort(e, axis=1)            # i ≤ j: a DAG plus self-loops
    rel = SparseRelation.from_coo(e, np.ones(len(e), bool), (N, N), "bool",
                                  capacity=int(len(np.unique(e, axis=0)))
                                  + (13 if case == "padded" else 0))
    if case != "dead_edge":
        return rel, rel
    nnz = int(rel.nnz)
    dead = rng.choice(nnz, 7, replace=False)
    values = np.asarray(rel.values).copy()
    values[dead] = False
    keep = np.setdiff1d(np.arange(nnz), dead)
    live = SparseRelation.from_coo(np.asarray(rel.coords)[keep],
                                   np.ones(len(keep), bool), (N, N), "bool")
    return SparseRelation(rel.coords, jnp.asarray(values), rel.nnz,
                          rel.shape, "bool"), live


def _carry(b: int, seed: int = 1):
    rng = np.random.default_rng(seed)
    y = np.zeros((b, N), bool)
    d = np.zeros((b, N), bool)
    d[np.arange(b), rng.integers(0, N, b)] = True
    return y, d, np.zeros(b, np.int32)


def _scatter_chunk(k: int):
    return jax.jit(lambda e, y, d, it: fx._chunk_loop(
        e, y, d, it, BOOL, k,
        advance=lambda dd: contract.spmm(e, dd, transpose=True)))


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("case", CASES)
def test_packed_chunks_match_scatter_chunks(case, b):
    """Three chained chunks of two rounds give the scatter chunk's
    ``(y, d, it)`` at every boundary, bit for bit."""
    rel, _ = _operator(case)
    packed, scatter = fx.CompiledChunk(2), _scatter_chunk(2)
    assert packed.packs(rel)
    p = s = _carry(b)
    for _ in range(3):
        p = packed(rel, *p)
        s = scatter(rel, *s)
        for got, want in zip(p, s):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        p = s = tuple(np.asarray(x) for x in s)


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("case", CASES)
def test_pull_round_matches_host_packed_round(case, b):
    """One round on random lanes against ``bool_round_packed`` over the
    live edges."""
    rel, live = _operator(case)
    d = np.random.default_rng(b).random((b, N)) < 0.3
    w = -(-b // 32)
    got = fx._unpack(fx._pull_round(fx.pull_view(rel), fx._pack(d, w)), b)
    plan = coo_spmm.plan_geometry(live, transpose=True)
    want = coo_spmm.unpack_lanes(
        coo_spmm.bool_round_packed(plan, coo_spmm.pack_lanes(d)), b)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("b", BATCHES)
def test_pack_round_trips_and_leaves_spare_lanes_zero(b):
    x = np.random.default_rng(b).random((b, N)) < 0.5
    w = -(-b // 32)
    words = fx._pack(x, w)
    assert words.shape == (w, N) and words.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(fx._unpack(words, b)), x)
    spare = np.asarray(fx._unpack(words, 32 * w))[b:]
    assert not spare.any()
    np.testing.assert_array_equal(np.asarray(fx._live_lanes(words, b)),
                                  x.any(axis=1))


def test_traced_edges_build_the_view_in_the_trace():
    """``_chunk_loop`` handed traced edges (``jit`` over the operator)
    builds its view inside the trace and agrees with the cached one."""
    rel, _ = _operator("padded")
    y, d, it = _carry(33)
    traced = jax.jit(lambda e, y, d, it: fx._chunk_loop(e, y, d, it, BOOL,
                                                        5))(rel, y, d, it)
    cached = fx.CompiledChunk(5)(rel, y, d, it)
    for got, want in zip(traced, cached):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_packed_chunk_holds_no_scatter_and_no_sort():
    rel, _ = _operator("padded")
    y, d, it = _carry(64)
    chunk = fx.CompiledChunk(4)
    hlo = chunk._jit.lower(rel, fx.pull_view(rel), y, d, it).as_text()
    assert "jit_fixpoint_chunk" in hlo and "stablehlo.gather" in hlo
    assert "stablehlo.scatter" not in hlo and "stablehlo.sort" not in hlo


# --------------------------------------------------------------------------
# who takes the packed round


def _no_packed_round(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("packed pull round taken")
    monkeypatch.setattr(fx, "_packed_chunk_loop", refuse)


def test_advance_override_keeps_the_scatter_chunk(monkeypatch):
    rel, _ = _operator("directed")
    y, d, it = _carry(8)
    want = fx.CompiledChunk(3)(rel, y, d, it)
    _no_packed_round(monkeypatch)
    assert not fx.takes_pull_round("bool", advance=lambda dd: dd)
    got = _scatter_chunk(3)(rel, y, d, it)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_active_mesh_keeps_the_scatter_chunk(monkeypatch):
    from repro.launch import mesh as mesh_mod
    from repro.launch import rules as rules_mod
    rel, _ = _operator("padded")
    y, d, it = _carry(8)
    want = fx.CompiledChunk(3)(rel, y, d, it)
    _no_packed_round(monkeypatch)
    mesh = mesh_mod.make_datalog_mesh(1)
    with sh.use_rules(mesh, rules_mod.make_rules(mesh, "datalog")):
        assert not fx.takes_pull_round("bool")
        got = fx._chunk_loop(rel, y, d, it, BOOL, 3)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_trop_chunk_keeps_the_scatter_chunk(monkeypatch):
    g = datasets.erdos_renyi(N, 2.5, seed=3, weighted=True)
    rel = g.sparse_adjacency(semiring="trop")
    trop = sr_mod.get("trop")
    y = np.full((4, N), np.inf, np.float32)
    d = np.full((4, N), np.inf, np.float32)
    d[np.arange(4), [0, 5, 9, 17]] = 0.0
    it = np.zeros(4, np.int32)
    _no_packed_round(monkeypatch)
    chunk = fx.CompiledChunk(3)
    assert not chunk.packs(rel)
    got = chunk(rel, y, d, it)
    want = fx._chunk_loop(rel, y, d, it, trop, 3, advance=lambda dd:
                          contract.spmm(rel, dd, transpose=True))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# the serve loop: engagement counter and the view's cache


def _server(semiring: str, n: int = 1024, **kw):
    cs = ContinuousServer(chunk_iters=2, warm_answers=0,
                          host_kernels=False, **kw)
    g = datasets.erdos_renyi_sparse(n, 3.0, seed=0)
    if semiring == "bool":
        db = engine.Database(programs.bm(a=0).original.schema, {"id": n},
                             {"E": g.sparse_adjacency(),
                              "V": jnp.ones((n,), bool)})
        cs.register("f", lambda a: programs.bm(a=a).optimized, db)
    else:
        mk = lambda a: programs.sssp(a=a, wmax=2, dmax=2 * n).optimized  # noqa
        db = engine.Database(programs.sssp(a=0, wmax=2, dmax=2 * n)
                             .original.schema,
                             {"id": n, "w": 2, "d": 2 * n}, {})
        cs.register("f", mk, db, edges=g.sparse_adjacency(semiring="trop"))
    return cs


@pytest.mark.parametrize("semiring", ["bool", "trop"])
def test_packed_rounds_in_stats_and_marks(semiring, tmp_path):
    """A scale-10 𝔹 server runs every round packed; a tropical one none.
    Each chunk's ``counters`` mark carries the increment."""
    cs = _server(semiring, max_batch=8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for s in range(0, 1024, 97):
            cs.submit("f", s)
        cs.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    stats = cs.stats()
    assert stats["rounds"] > 0
    assert stats["packed_rounds"] == (stats["rounds"] if semiring == "bool"
                                      else 0)
    (path,) = tmp_path.rglob("*.xplane.pb")
    marks = [dict(e.stats)
             for p in jax.profiler.ProfileData.from_file(str(path)).planes
             if p.name.startswith("/host:") for ln in p.lines
             for e in ln.events if e.name == "counters"]
    assert len(marks) == stats["chunks"]
    assert sum(m["packed_rounds"] for m in marks) == stats["packed_rounds"]


def test_pull_view_built_once_per_operator(monkeypatch):
    """Chunks and pools on the same edges share one device-resident
    view, which the carry's byte count never includes."""
    builds = []
    real = fx._build_pull_view

    def counting(edges, **kw):
        builds.append(kw)
        return real(edges, **kw)

    monkeypatch.setattr(fx, "_build_pull_view", counting)
    cs = _server("bool", n=256, max_batch=8)
    fam_edges = cs._families["f"].fam.edges
    fx._PULL_CACHE.clear()
    for s in (3, 7):                      # two: one would skip the pool
        cs.submit("f", s)
    cs.run_until_idle()
    first = cs.stats()
    assert first["chunks"] > 1 and len(builds) == 1
    b = first["families"]["f"]["pool_b"]
    assert first["carry_bytes"] == first["chunks"] * (2 * 2 * b * 256
                                                      + 2 * 4 * b)
    for s in range(8):                    # a wider pool on the same edges
        cs.submit("f", 10 + s)
    cs.run_until_idle()
    assert cs.stats()["families"]["f"]["pool_b"] > b
    assert cs.stats()["chunks"] > first["chunks"]
    assert builds == [{"concrete": True}]
    assert fx.pull_view(fam_edges.as_jnp()) is fx.pull_view(fam_edges)
