"""The continuous-batching scheduler (repro.serve, DESIGN.md §7):
slot admission/eviction exactness on every stepper, update fencing,
FIFO-per-family delivery, weighted fairness, backpressure, the bounded
caches, the latency histograms, and the B=1 latency-route regression."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.program import run_program
from repro.datalog import datasets, programs
from repro.launch.datalog_serve import DatalogServer
from repro.serve import (BackpressureError, ContinuousServer, LRUCache,
                         LatencyHistogram)
from repro.serve.slots import LevelSyncTropStepper
from repro.sparse import SparseRelation, sparse_seminaive_fixpoint


def _bm_db(n=120, seed=2, sparse=True):
    g = datasets.erdos_renyi(n, 3.0, seed=seed)
    schema = programs.bm(a=0).original.schema
    e = g.sparse_adjacency() if sparse else g.adjacency()
    return g, engine.Database(schema, {"id": n},
                              {"E": e, "V": jnp.ones((n,), bool)})


def _expected_bm(db, source):
    dense_db = db.with_storage("E", "dense")
    ans, _ = run_program(programs.bm(a=source).optimized, dense_db,
                         mode="seminaive")
    return np.asarray(ans)


def _sssp_setup(n=90, wmax=4, seed=3):
    g = datasets.erdos_renyi(n, 3.0, seed=seed, weighted=True, wmax=wmax)
    b = programs.sssp(a=0, wmax=wmax, dmax=12 * wmax)
    return g, b.make_db(g), (
        lambda a: programs.sssp(a=a, wmax=wmax, dmax=12 * wmax).optimized)


def _expected_sssp(db, mk, source):
    ans, _ = run_program(mk(source), db, mode="seminaive")
    return np.asarray(ans)


# --------------------------------------------------------------------------
# bounded caches & histograms
# --------------------------------------------------------------------------


def test_lru_cache_eviction_and_counters():
    c = LRUCache(2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1           # refreshes a's recency
    c.put("c", 3)                    # evicts b, the least recent
    assert c.get("b") is None
    assert c.get("a") == 1 and c.get("c") == 3
    assert (c.hits, c.misses, c.evictions) == (3, 1, 1)
    assert c.peek("a") == 1 and c.hits == 3  # peek: uncounted
    assert c.clear() == 2 and len(c) == 0


def test_lru_cache_zero_capacity_drops():
    c = LRUCache(0)
    c.put("a", 1)
    assert c.get("a") is None and len(c) == 0


def test_latency_histogram_quantiles():
    h = LatencyHistogram()
    for ms in range(1, 101):         # 1ms … 100ms uniformly
        h.record(ms * 1e-3)
    s = h.summary()
    assert s["count"] == 100
    # log-bucketed: ~4.4% resolution per bucket
    assert s["p50_ms"] == pytest.approx(50, rel=0.15)
    assert s["p95_ms"] == pytest.approx(95, rel=0.15)
    assert s["p99_ms"] == pytest.approx(99, rel=0.15)
    assert s["max_ms"] >= s["p99_ms"]


# --------------------------------------------------------------------------
# exactness: every stepper matches the single-source engine
# --------------------------------------------------------------------------


def test_continuous_bool_bitset_exact():
    """CPU boolean families ride the lane-bitset stepper; every answer
    must equal the engine's single-source run."""
    _, db = _bm_db()
    cs = ContinuousServer(max_batch=8, chunk_iters=3, warm_answers=0)
    cs.register("reach", lambda a: programs.bm(a=a).optimized, db)
    rng = np.random.default_rng(0)
    reqs = [cs.submit("reach", int(s)) for s in rng.integers(0, 120, 20)]
    assert cs.run_until_idle() == 20
    for r in reqs:
        assert r.error is None, r.error
        assert np.array_equal(np.asarray(r.result),
                              _expected_bm(db, r.source)), r.source
    st = cs.stats()
    assert st["evicted"] + st["latency_routed"] == 20
    assert st["packed_fallback"] == 0


def test_continuous_trop_level_sync_exact():
    """Integer-weighted SSSP rides the level-synchronous BFS stepper."""
    g, db, mk = _sssp_setup()
    cs = ContinuousServer(max_batch=8, chunk_iters=3, warm_answers=0)
    cs.register("sssp", mk, db, edges=g.sparse_adjacency(semiring="trop"))
    rng = np.random.default_rng(1)
    reqs = [cs.submit("sssp", int(s)) for s in rng.integers(0, g.n, 16)]
    cs.run_until_idle()
    for r in reqs:
        assert r.error is None, r.error
        assert np.array_equal(np.asarray(r.result),
                              _expected_sssp(db, mk, r.source)), r.source


def test_continuous_jax_chunk_stepper_exact():
    """host_kernels=False forces the jitted chunked-while-loop stepper;
    it must agree bit-for-bit with the host kernels' answers."""
    _, db = _bm_db()
    cs = ContinuousServer(max_batch=8, chunk_iters=2, warm_answers=0,
                          host_kernels=False)
    cs.register("reach", lambda a: programs.bm(a=a).optimized, db)
    rng = np.random.default_rng(2)
    reqs = [cs.submit("reach", int(s)) for s in rng.integers(0, 120, 12)]
    cs.run_until_idle()
    for r in reqs:
        assert np.array_equal(np.asarray(r.result),
                              _expected_bm(db, r.source)), r.source
    assert cs.stats()["compile_cache"]["misses"] >= 1


@pytest.mark.parametrize("semiring", ["bool", "trop"])
def test_device_pool_exact_through_migration_and_fence(semiring):
    """The device-resident pool (host_kernels=False) answers every query
    bit for bit as the single-source engine, rounds included, through
    admission, harvest, migration to a larger bucket and the update
    fence; ``rows_in`` counts every row spliced (a migrated row twice)
    and ``rows_out`` every row copied out."""
    from repro.serve import family as fam_mod
    from repro.sparse import fixpoint as fx

    cs = ContinuousServer(max_batch=8, chunk_iters=2, warm_answers=0,
                          host_kernels=False)
    if semiring == "bool":
        g, db = _bm_db()
        cs.register("f", lambda a: programs.bm(a=a).optimized, db)
        update = ([[0, g.n - 1]], None)
    else:
        g, db, mk = _sssp_setup()
        cs.register("f", mk, db, edges=g.sparse_adjacency(semiring="trop"))
        update = ([[0, g.n - 1]], [1.0])
    fs = cs._families["f"]
    before = [cs.submit("f", s) for s in (1, 5)]
    cs.step()                        # a B=2 pool, its rows on the device
    before += [cs.submit("f", s) for s in range(10, 16)]
    cs.step()                        # demand of 8: migrate to B=8
    assert cs.stats()["migrated"] >= 1
    assert cs.stats()["families"]["f"]["pool_b"] == 8
    before += [cs.submit("f", s) for s in range(30, 90, 5)]  # reuse lanes
    edges_before = fs.fam.edges
    cs.submit_update("f", *update)
    after = [cs.submit("f", s) for s in (0, 20, 40)]
    cs.run_until_idle()
    assert fs.fam.edges is not edges_before
    for reqs, edges in ((before, edges_before), (after, fs.fam.edges)):
        for r in reqs:
            y, iters = fx.fixpoint(edges, fam_mod.family_init(fs.fam,
                                                               r.source))
            assert np.array_equal(np.asarray(r.result), np.asarray(y)), \
                r.source
            assert r.iters == int(iters), r.source
    st = cs.stats()
    assert st["updates"] == 1 and st["latency_routed"] == 0
    assert st["admitted"] == st["evicted"] == len(before) + len(after)
    assert st["rows_in"] == st["admitted"] + st["migrated"]
    assert st["rows_out"] == st["evicted"]


def test_continuous_dense_packed_fallback():
    """A dense-operator family has no columnwise splice: the scheduler
    serves it through the packed whole-run fallback, still exactly."""
    _, db = _bm_db(sparse=False)
    cs = ContinuousServer(max_batch=4, warm_answers=0)
    cs.register("reach", lambda a: programs.bm(a=a).optimized, db)
    reqs = [cs.submit("reach", s) for s in (3, 14, 15, 92, 65)]
    cs.run_until_idle()
    for r in reqs:
        assert np.array_equal(np.asarray(r.result),
                              _expected_bm(db, r.source)), r.source
    assert cs.stats()["packed_fallback"] >= 1


def test_trop_stepper_refuses_finite_nonzero_init():
    """Only {0, ∞} init vectors encode as a level-0 BFS frontier; any
    other init must be refused at admission (scheduler then serves it
    solo) — never silently mis-encoded."""
    g, _, _ = _sssp_setup()
    st = LevelSyncTropStepper(
        g.sparse_adjacency(semiring="trop").as_jnp(), g.n, 4)
    bad = np.full(g.n, np.inf, np.float32)
    bad[3] = 2.0                     # finite but not the semiring one
    assert st.admit(0, bad) is False
    ok = np.full(g.n, np.inf, np.float32)
    ok[3] = 0.0
    assert st.admit(0, ok) is True


def test_trop_stepper_rejects_fractional_weights():
    g0 = datasets.erdos_renyi(40, 3.0, seed=5)
    w = np.full(len(g0.edges), 1.5, np.float32)
    rel = SparseRelation.from_coo(g0.edges, w, (40, 40), "trop")
    with pytest.raises(ValueError):
        LevelSyncTropStepper(rel, 40, 4)


def test_multi_chunk_long_chain_no_early_harvest():
    """A path graph needs ~n GSN rounds: with a tiny chunk the row must
    survive many chunk boundaries before its mask fires, and the answer
    must be the full chain (an early harvest would truncate it)."""
    n = 64
    g = datasets.path_graph(n)
    schema = programs.bm(a=0).original.schema
    db = engine.Database(schema, {"id": n},
                         {"E": g.sparse_adjacency(),
                          "V": jnp.ones((n,), bool)})
    for hk in (True, False):
        cs = ContinuousServer(max_batch=4, chunk_iters=2,
                              warm_answers=0, host_kernels=hk)
        cs.register("reach", lambda a: programs.bm(a=a).optimized, db)
        r0 = cs.submit("reach", 0)   # reaches all n vertices
        r1 = cs.submit("reach", n - 2)  # reaches one
        cs.run_until_idle()
        assert np.asarray(r0.result).sum() == n
        assert np.asarray(r1.result).sum() == 2
        assert r0.iters >= n - 2     # many chunks, counted exactly
        assert cs.stats()["chunks"] >= (n - 2) // 2


# --------------------------------------------------------------------------
# scheduling semantics
# --------------------------------------------------------------------------


def test_slots_reused_across_stream():
    """More requests than slots: the pool must recycle freed rows (one
    pool, many admissions) instead of growing or re-pooling."""
    _, db = _bm_db()
    cs = ContinuousServer(max_batch=4, chunk_iters=2, warm_answers=0)
    cs.register("reach", lambda a: programs.bm(a=a).optimized, db)
    rng = np.random.default_rng(3)
    reqs = [cs.submit("reach", int(s)) for s in rng.integers(0, 120, 20)]
    cs.run_until_idle()
    st = cs.stats()
    assert st["admitted"] == 20 and st["evicted"] == 20
    assert st["families"]["reach"]["pool_b"] == 4
    for r in reqs:
        assert np.array_equal(np.asarray(r.result),
                              _expected_bm(db, r.source))


def test_fifo_delivery_per_family():
    """Rows converge out of order; answers still publish in submission
    order within a family."""
    _, db = _bm_db()
    cs = ContinuousServer(max_batch=8, chunk_iters=1, warm_answers=0)
    cs.register("reach", lambda a: programs.bm(a=a).optimized, db)
    reqs = [cs.submit("reach", int(s)) for s in
            np.random.default_rng(4).integers(0, 120, 12)]
    delivered = []
    while cs.pending():
        delivered.extend(cs.step())
    assert delivered == reqs
    dones = [r.done_s for r in reqs]
    assert dones == sorted(dones)


def test_update_fence_orders_answers():
    """A query submitted before an edge merge answers from the old
    graph; one submitted after answers from the new graph — even though
    both may sit queued at the same time."""
    n = 16
    edges = np.array([[i, i + 1] for i in range(6)])  # 0→…→6, 7+ isolated
    rel = SparseRelation.from_coo(
        edges, np.ones(len(edges), bool), (n, n), "bool")
    schema = programs.bm(a=0).original.schema
    db = engine.Database(schema, {"id": n},
                         {"E": rel, "V": jnp.ones((n,), bool)})
    cs = ContinuousServer(max_batch=4, chunk_iters=1, warm_answers=0)
    cs.register("reach", lambda a: programs.bm(a=a).optimized, db)
    q_before = cs.submit("reach", 0)
    u = cs.submit_update("reach", [[6, 9]])   # bridge to vertex 9
    q_after = cs.submit("reach", 0)
    cs.run_until_idle()
    assert u.applied
    before, after = np.asarray(q_before.result), np.asarray(q_after.result)
    assert not before[9] and before.sum() == 7
    assert after[9] and after.sum() == 8


def test_update_delete_repairs_warm_answers():
    _, db = _bm_db()
    cs = ContinuousServer(max_batch=4)
    cs.register("reach", lambda a: programs.bm(a=a).optimized, db)
    cs.submit("reach", 5)
    cs.run_until_idle()
    r_warm = cs.submit("reach", 5)
    cs.run_until_idle()
    assert cs.stats()["warm_hits"] == 1 and r_warm.iters == 0
    eh = db.relations["E"].as_np()
    e0 = np.asarray(eh.coords[:1])
    u = cs.submit_update("reach", e0, op="delete")
    r_next = cs.submit("reach", 5)
    cs.run_until_idle()
    # the synthesized maintenance rule (DESIGN.md §11) repairs the
    # cached answer in place instead of dropping it
    assert u.applied and cs.stats()["answers_dropped"] == 0
    assert cs.stats()["answers_repaired"] >= 1
    assert cs.stats()["warm_hits"] == 2, \
        "the post-delete query should warm-hit the repaired answer"
    db2 = engine.Database(db.schema, db.domains,
                          {"E": db.relations["E"].delete_keys(e0),
                           "V": db.relations["V"]})
    assert np.array_equal(np.asarray(r_next.result), _expected_bm(db2, 5))


def test_backpressure_sheds_at_queue_limit():
    _, db = _bm_db()
    cs = ContinuousServer(max_batch=4, queue_limit=3)
    cs.register("reach", lambda a: programs.bm(a=a).optimized, db)
    ok, shed = 0, 0
    for s in range(8):
        try:
            cs.submit("reach", s)
            ok += 1
        except BackpressureError as e:
            assert e.family == "reach" and e.limit == 3
            shed += 1
    assert (ok, shed) == (3, 5) and cs.stats()["shed"] == 5
    cs.run_until_idle()
    assert cs.stats()["served"] == 3
    # updates are never shed, even at the bound
    eh = db.relations["E"].as_np()
    cs.submit("reach", 9)            # refill to the limit... almost
    cs.submit_update("reach", np.asarray(eh.coords[:1]), op="delete")
    cs.run_until_idle()
    assert cs.stats()["updates"] == 1


def test_weighted_fairness_no_starvation():
    """A deep queue on one family cannot starve another: each family
    advances every scheduling round, so the light family finishes while
    the heavy backlog is still draining."""
    _, db = _bm_db()
    g2, db2, mk2 = _sssp_setup()
    cs = ContinuousServer(max_batch=4, chunk_iters=1, warm_answers=0)
    cs.register("heavy", lambda a: programs.bm(a=a).optimized, db)
    cs.register("light", mk2, db2,
                edges=g2.sparse_adjacency(semiring="trop"))
    rng = np.random.default_rng(6)
    heavy = [cs.submit("heavy", int(s)) for s in rng.integers(0, 120, 40)]
    light = [cs.submit("light", int(s)) for s in rng.integers(0, g2.n, 3)]
    while any(r.done_s == 0.0 for r in light):
        assert cs.step() is not None
    assert sum(r.done_s > 0.0 for r in heavy) < len(heavy)
    cs.run_until_idle()
    for r in light:
        assert np.array_equal(np.asarray(r.result),
                              _expected_sssp(db2, mk2, r.source))
    for r in heavy:
        assert np.array_equal(np.asarray(r.result),
                              _expected_bm(db, r.source))


def test_register_weight_validation():
    _, db = _bm_db()
    cs = ContinuousServer()
    with pytest.raises(ValueError):
        cs.register("reach", lambda a: programs.bm(a=a).optimized, db,
                    weight=0)


def test_bad_source_fails_without_stranding():
    """A source whose program changes the linear operator fails its own
    request only."""
    _, db = _bm_db()
    cs = ContinuousServer(max_batch=4, warm_answers=0)

    def mk(a):
        if a == 999:                 # different operator shape
            return programs.sssp(a=0, wmax=4, dmax=16).optimized
        return programs.bm(a=a).optimized

    cs.register("reach", mk, db)
    good = [cs.submit("reach", s) for s in (1, 2)]
    bad = cs.submit("reach", 999)
    more = cs.submit("reach", 3)
    cs.run_until_idle()
    assert bad.result is None and bad.error
    assert cs.stats()["failed"] == 1
    for r in (*good, more):
        assert np.array_equal(np.asarray(r.result),
                              _expected_bm(db, r.source))


def test_fast_init_matches_eval_and_rejects_operator_swap():
    """The probed one-hot init fast path must produce exactly the
    evaluated init, and fall back (to the erroring slow path) for a
    source whose program is not the template with the source constant
    substituted."""
    from repro.core import planner
    from repro.serve import family as fam_mod

    _, db = _bm_db()
    g, ss_db, mk_ss = _sssp_setup()

    def mk_bm(a):
        if a == 7:                    # operator swap at an in-range source
            return programs.cc().optimized
        return programs.bm(a=a).optimized

    for mk, d in ((mk_bm, db), (mk_ss, ss_db)):
        fam = fam_mod.build_family("f", mk, d)
        assert fam.fast_init is not None
        for s in (0, 1, 5, fam.n - 1):
            prog = mk(s)
            expect = planner.source_init(fam.plan, prog, fam.host_db,
                                         hints=dict(prog.sort_hints),
                                         backend="np")
            got = fam_mod.family_init(fam, s)
            assert got.dtype == np.asarray(expect).dtype
            assert np.array_equal(got, expect), s

    fam = fam_mod.build_family("reach", mk_bm, db)
    with pytest.raises(Exception, match="linear operator"):
        fam_mod.family_init(fam, 7)   # structural check must not pass it


# --------------------------------------------------------------------------
# bounded compile cache
# --------------------------------------------------------------------------


def test_compile_cache_lru_bound_continuous():
    """compiled_cache=1 with two bucket sizes forces evictions; results
    stay exact (an evicted runner just recompiles)."""
    _, db = _bm_db()
    cs = ContinuousServer(max_batch=8, warm_answers=0, compiled_cache=1,
                          host_kernels=False)
    cs.register("reach", lambda a: programs.bm(a=a).optimized, db)
    # pools grow only, so drive demand upward: bucket 2 → 4 → 8
    reqs = [cs.submit("reach", s) for s in (1, 2)]
    cs.run_until_idle()
    reqs += [cs.submit("reach", s) for s in (3, 4, 5)]
    cs.run_until_idle()
    reqs += [cs.submit("reach", s) for s in range(8)]
    cs.run_until_idle()
    cc = cs.stats()["compile_cache"]
    assert cc["size"] == 1 and cc["evictions"] >= 2
    for r in reqs:
        assert np.array_equal(np.asarray(r.result),
                              _expected_bm(db, r.source))


def test_compile_cache_lru_bound_shim():
    """The packed shim's compile cache honors the same bound and
    surfaces evictions in its stats dict."""
    _, db = _bm_db()
    server = DatalogServer(max_batch=8, warm_answers=0, compiled_cache=1)
    server.register("reach", lambda a: programs.bm(a=a).optimized, db)
    for batch in ((1, 2), tuple(range(8)), (11, 12)):
        for s in batch:
            server.submit("reach", s)
        server.run_until_idle()
    assert server.stats["cache_evictions"] >= 2
    assert server.stats["cache_misses"] >= 3


def test_warm_answer_lru_bound():
    """The warm-answer store is capacity-bounded: old entries evict and
    re-serve cold (counted), instead of growing without bound."""
    _, db = _bm_db()
    cs = ContinuousServer(max_batch=4, warm_answers=2)
    cs.register("reach", lambda a: programs.bm(a=a).optimized, db)
    for s in (1, 2, 3):              # 3 distinct answers, capacity 2
        cs.submit("reach", s)
    cs.run_until_idle()
    fam_stats = cs.stats()["families"]["reach"]
    assert fam_stats["warm_answers"] == 2
    assert fam_stats["warm_evictions"] >= 1
    r = cs.submit("reach", 1)        # evicted → cold, still exact
    cs.run_until_idle()
    assert cs.stats()["warm_hits"] == 0 and r.iters >= 1
    assert np.array_equal(np.asarray(r.result), _expected_bm(db, 1))


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------


def test_stats_latency_and_gauges():
    _, db = _bm_db()
    cs = ContinuousServer(max_batch=4, warm_answers=0)
    cs.register("reach", lambda a: programs.bm(a=a).optimized, db)
    for s in range(6):
        cs.submit("reach", s)
    cs.run_until_idle()
    st = cs.stats()
    lat = st["latency"]["total"]
    assert lat["count"] == 6
    assert 0 < lat["p50_ms"] <= lat["p95_ms"] <= lat["p99_ms"]
    assert st["families"]["reach"]["queue_depth"] == 0
    assert st["families"]["reach"]["in_flight"] == 0
    assert st["families"]["reach"]["served"] == 6


# --------------------------------------------------------------------------
# B=1 regression: the latency route must beat the (1, n) batched loop
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_single_request_latency_route_beats_loop():
    """ISSUE 6 satellite: serving B=1 requests must be at least as fast
    as the naive per-source jitted loop (it was 0.81× before the
    frontier routing).  Generous margin — the frontier path measures
    ~5-7× the loop on this shape."""
    if jax.default_backend() != "cpu":
        pytest.skip("latency routing is the CPU frontier path")
    n = 5000
    g = datasets.powerlaw(n, 4, seed=1)
    rel = g.sparse_adjacency().as_jnp()
    schema = programs.bm(a=0).original.schema
    db = engine.Database(schema, {"id": n},
                         {"E": rel, "V": jnp.ones((n,), bool)})
    server = DatalogServer(max_batch=64, warm_answers=0)
    server.register("reach", lambda a: programs.bm(a=a).optimized, db)

    single = jax.jit(lambda e, i: sparse_seminaive_fixpoint(
        e, i, mode="jit"))

    def one_hot(s):
        v = np.zeros(n, bool)
        v[s] = True
        return jnp.asarray(v)

    jax.block_until_ready(single(rel, one_hot(0))[0])   # warm the jit
    q = server.submit("reach", 0)
    server.run_until_idle()                              # warm the route

    sources = [7, 501, 2003, 3999, 4444]
    t0 = time.perf_counter()
    loop_out = [np.asarray(single(rel, one_hot(s))[0]) for s in sources]
    t_loop = time.perf_counter() - t0

    reqs = []
    t0 = time.perf_counter()
    for s in sources:                # one at a time: every serve is B=1
        reqs.append(server.submit("reach", s))
        server.run_until_idle()
    t_serve = time.perf_counter() - t0

    assert server.stats["latency_routed"] == len(sources) + 1
    for r, y in zip(reqs, loop_out):
        assert np.array_equal(np.asarray(r.result), y)
    assert t_serve <= t_loop * 1.2, \
        f"B=1 serve {t_serve:.3f}s slower than loop {t_loop:.3f}s"
