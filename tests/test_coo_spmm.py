"""Fused COO semiring SpMM (DESIGN.md §9, ``kernels/coo_spmm.py``):

* Pallas kernel parity vs the jnp gather→⊗→segment-⊕ oracle across all
  four semirings, ragged nnz tails (empty / duplicate / off-block
  shapes), (B, n) batching, and both transpose orientations — in
  interpret mode so CI's CPU job exercises the kernel path.
* Host fused executors (``spmm_host``, packed-𝔹 ``bool_round_packed``)
  against the same oracle.
* Fixpoint parity — values AND per-row iteration counts — of the
  fused/pallas backends vs the jnp staged loop, single and batched,
  plus the warm resume-chunk carry the continuous serve loop compiles.
* Planner crossover pinning: ``sparse_frontier_pallas`` is picked
  exactly where ``SpmmKernelModel`` says the measured win exists, and
  rejected (with the right reason) everywhere else; monkeypatching the
  measured constants flips the pick at both extremes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, planner
from repro.core import semiring as sr_mod
from repro.core.program import run_program
from repro.datalog import datasets, programs
from repro.kernels import coo_spmm
from repro.kernels import ops as kops
from repro.sparse import contract
from repro.sparse.coo import SparseRelation
from repro.sparse.fixpoint import (resume_fixpoint_chunk,
                                   sparse_seminaive_fixpoint)

CPU = jax.default_backend() == "cpu"
SEMIRINGS = ("bool", "trop", "nat", "maxplus")


def _relation(n, avg_deg, sr_name, seed, lib="jnp"):
    g = datasets.powerlaw(n, avg_deg, seed=seed)
    rng = np.random.default_rng(seed + 1)
    edges = g.edges
    if sr_name == "maxplus":
        # longest-path diverges on cycles (⊕=max keeps growing); orient
        # low→high so the fixpoint converges in O(depth) rounds
        edges = np.sort(edges, axis=1)
        edges = edges[edges[:, 0] != edges[:, 1]]
    w = rng.integers(1, 5, len(edges))
    if sr_name == "bool":
        rel = datasets.Graph(n, edges, w).sparse_adjacency()
    else:
        rel = SparseRelation.from_coo(edges, w, (n, n), sr_name)
    return rel.as_jnp() if lib == "jnp" else rel


def _frontier(n, b, sr_name, seed, live_frac=0.1):
    rng = np.random.default_rng(seed)
    live = rng.random((n, b)) < live_frac
    srn = sr_mod.get(sr_name, lib="np")
    if sr_name == "bool":
        return live
    x = np.full((n, b), srn.zero, srn.dtype)
    x[live] = rng.integers(0, 8, int(live.sum())).astype(srn.dtype)
    return x


def _oracle(rel, x, transpose):
    xj = jnp.asarray(x)
    if xj.ndim == 1:  # the jnp oracle is the batched (n, B) contraction
        return np.asarray(contract.spmm(rel, xj[:, None],
                                        transpose=transpose))[:, 0]
    return np.asarray(contract.spmm(rel, xj, transpose=transpose))


# --------------------------------------------------------------------------
# Pallas kernel parity (interpret mode — the CI CPU path)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("sr_name", SEMIRINGS)
@pytest.mark.parametrize("transpose", [False, True])
def test_pallas_batched_parity(sr_name, transpose):
    n = 300  # off every block multiple: dot (256,256,128), minmax 32³
    rel = _relation(n, 3, sr_name, seed=11)
    plan = coo_spmm.plan_geometry(rel, transpose=transpose)
    x = _frontier(n, 8, sr_name, seed=5)
    got = np.asarray(coo_spmm.spmm_pallas(plan, x, interpret=True))
    assert np.array_equal(got, _oracle(rel, x, transpose)), sr_name


@pytest.mark.parametrize("sr_name", SEMIRINGS)
def test_pallas_single_vector_parity(sr_name):
    n = 130
    rel = _relation(n, 4, sr_name, seed=3)
    plan = coo_spmm.plan_geometry(rel, transpose=True)
    x = _frontier(n, 1, sr_name, seed=9)[:, 0]
    got = np.asarray(coo_spmm.spmm_pallas(plan, x, interpret=True))
    assert got.shape == (n,)
    assert np.array_equal(got, _oracle(rel, x, True))


@pytest.mark.parametrize("sr_name", ["bool", "trop"])
def test_pallas_empty_operator(sr_name):
    n = 64
    rel = SparseRelation.from_coo(np.zeros((0, 2), np.int64),
                                  np.zeros((0,)), (n, n), sr_name)
    plan = coo_spmm.plan_geometry(rel, transpose=True)
    assert plan.nnz == 0
    x = _frontier(n, 4, sr_name, seed=1)
    got = np.asarray(coo_spmm.spmm_pallas(plan, x, interpret=True))
    srn = sr_mod.get(sr_name, lib="np")
    assert np.array_equal(got, np.full((n, 4), srn.zero, srn.dtype))


@pytest.mark.parametrize("sr_name", ["trop", "nat"])
def test_pallas_duplicate_edges_coalesce(sr_name):
    """from_coo ⊕-coalesces duplicates; kernel and oracle must agree on
    the coalesced operator."""
    rng = np.random.default_rng(7)
    n = 80
    coords = rng.integers(0, n, (400, 2))  # heavy duplication
    vals = rng.integers(1, 6, 400)
    rel = SparseRelation.from_coo(coords, vals, (n, n), sr_name)
    plan = coo_spmm.plan_geometry(rel, transpose=True)
    x = _frontier(n, 8, sr_name, seed=2)
    got = np.asarray(coo_spmm.spmm_pallas(plan, x, interpret=True))
    assert np.array_equal(got, _oracle(rel, x, True))


def test_pallas_ragged_nnz_tail():
    """nnz far from a bk=256 multiple + n far from block multiples: pad
    slots must contribute the ⊕-identity, not junk."""
    rel = _relation(257, 5, "bool", seed=13)  # nnz ≈ 1285 = 5×257
    plan = coo_spmm.plan_geometry(rel, transpose=True)
    assert plan.nnz % plan.bk != 0
    x = _frontier(257, 3, "bool", seed=4)
    got = np.asarray(coo_spmm.spmm_pallas(plan, x, interpret=True))
    assert np.array_equal(got, _oracle(rel, x, True))


# --------------------------------------------------------------------------
# host fused executors
# --------------------------------------------------------------------------


@pytest.mark.parametrize("sr_name", SEMIRINGS)
@pytest.mark.parametrize("transpose", [False, True])
def test_spmm_host_parity(sr_name, transpose):
    n = 220
    rel = _relation(n, 4, sr_name, seed=21)
    plan = coo_spmm.plan_geometry(rel, transpose=transpose)
    x = _frontier(n, 8, sr_name, seed=6)
    got = coo_spmm.spmm_host(plan, x)
    assert np.array_equal(got, _oracle(rel, x, transpose))
    x1 = x[:, 0]
    got1 = coo_spmm.spmm_host(plan, x1)
    assert got1.shape == (n,)
    assert np.array_equal(got1, _oracle(rel, x1, transpose))


@pytest.mark.parametrize("b", [1, 8, 64, 70])
def test_bool_round_packed_parity(b):
    """Packed-𝔹 round across word boundaries: 1 lane, full word, exact
    multiple, and a ragged 2-word tail."""
    n = 220
    rel = _relation(n, 4, "bool", seed=21)
    plan = coo_spmm.plan_geometry(rel, transpose=True)
    x = _frontier(n, b, "bool", seed=b)
    words = coo_spmm.pack_lanes(x.T)
    assert words.shape == (n, max(1, -(-b // 64)))
    got = coo_spmm.unpack_lanes(
        coo_spmm.bool_round_packed(plan, words), b).T
    assert np.array_equal(got, _oracle(rel, x, True))


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    x = rng.random((70, 150)) < 0.3  # (B, n), B off a word boundary
    assert np.array_equal(
        coo_spmm.unpack_lanes(coo_spmm.pack_lanes(x), 70), x)


# --------------------------------------------------------------------------
# geometry plan discipline
# --------------------------------------------------------------------------


def test_plan_geometry_cached_per_operator():
    rel = _relation(100, 3, "bool", seed=1)
    p1 = coo_spmm.plan_geometry(rel, transpose=True)
    p2 = coo_spmm.plan_geometry(rel, transpose=True)
    assert p1 is p2
    assert coo_spmm.plan_geometry(rel, transpose=False) is not p1
    # as_jnp on a jnp-backed relation preserves buffer identity — the
    # serve loop's repeat calls must hit the same plan (and jit_cache)
    assert coo_spmm.plan_geometry(rel.as_jnp(), transpose=True) is p1


def test_plan_geometry_rejects_tracers():
    rel = _relation(50, 3, "bool", seed=2)

    @jax.jit
    def bad(coords, values):
        r = SparseRelation(coords, values, rel.shape, rel.semiring,
                           rel.nnz)
        coo_spmm.plan_geometry(r, transpose=True)
        return coords

    with pytest.raises(ValueError, match="concrete operator"):
        bad(rel.coords, rel.values)


# --------------------------------------------------------------------------
# fixpoint parity: values AND per-row iteration counts
# --------------------------------------------------------------------------


@pytest.mark.parametrize("sr_name", ["bool", "trop", "maxplus"])
@pytest.mark.parametrize("backend", ["fused", "pallas"])
def test_fixpoint_backend_parity_batched(sr_name, backend):
    n, b = 240, 6
    rel = _relation(n, 3, sr_name, seed=31)
    srn = sr_mod.get(sr_name, lib="np")
    init = np.full((b, n), srn.zero, srn.dtype)
    for i in range(b):
        init[i, (i * 17) % n] = srn.one
    want_x, want_it = sparse_seminaive_fixpoint(rel, jnp.asarray(init),
                                                mode="jit")
    got_x, got_it = sparse_seminaive_fixpoint(rel, jnp.asarray(init),
                                              mode="jit", backend=backend)
    assert np.array_equal(np.asarray(got_x), np.asarray(want_x)), sr_name
    assert np.array_equal(np.asarray(got_it), np.asarray(want_it))


@pytest.mark.parametrize("backend", ["fused", "pallas"])
def test_fixpoint_backend_parity_single(backend):
    n = 180
    rel = _relation(n, 3, "trop", seed=8)
    init = np.full(n, np.inf, np.float32)
    init[0] = 0.0
    want_x, want_it = sparse_seminaive_fixpoint(rel, jnp.asarray(init),
                                                mode="jit")
    got_x, got_it = sparse_seminaive_fixpoint(rel, jnp.asarray(init),
                                              mode="jit", backend=backend)
    assert np.array_equal(np.asarray(got_x), np.asarray(want_x))
    assert int(got_it) == int(want_it)


@pytest.mark.parametrize("backend", ["fused", "pallas"])
def test_resume_chunk_backend_parity(backend):
    """The serve loop's compiled unit: chained bounded chunks must carry
    (y, Δ, it) identically to the jnp chunk body."""
    n, b = 200, 5
    rel = _relation(n, 3, "bool", seed=41)
    init = np.zeros((b, n), bool)
    init[np.arange(b), np.arange(b) * 13] = True
    y_j = d_j = jnp.asarray(init)
    y_f, d_f = np.asarray(init), np.asarray(init)
    it_j = jnp.zeros(b, jnp.int32)
    it_f = np.zeros(b, np.int32)
    for _ in range(4):
        y_j, d_j, it_j = resume_fixpoint_chunk(rel, y_j, d_j, it_j,
                                               max_iters=3)
        y_f, d_f, it_f = resume_fixpoint_chunk(rel, y_f, d_f, it_f,
                                               max_iters=3,
                                               backend=backend)
        assert np.array_equal(np.asarray(y_f), np.asarray(y_j))
        assert np.array_equal(np.asarray(d_f), np.asarray(d_j))
        assert np.array_equal(np.asarray(it_f), np.asarray(it_j))


# --------------------------------------------------------------------------
# planner crossover pinning (both extremes)
# --------------------------------------------------------------------------


def _bool_plan(n, objective="throughput", avg_deg=3.0):
    g = datasets.erdos_renyi(n, avg_deg, seed=2)
    schema = programs.bm(a=0).original.schema
    db = engine.Database(schema, {"id": n},
                         {"E": g.sparse_adjacency(),
                          "V": jnp.ones((n,), bool)})
    return planner.plan_program(programs.bm(a=0).optimized, db,
                                objective=objective)


def _trop_plan(n, objective="throughput", avg_deg=3.0):
    b = programs.sssp(a=0, wmax=4, dmax=40)
    g = datasets.erdos_renyi(n, avg_deg, seed=4, weighted=True, wmax=4)
    db = engine.Database(b.original.schema, {"id": n, "w": 4, "d": 40}, {})
    return planner.plan_program(b.optimized, db, objective=objective,
                                edges=g.sparse_adjacency(semiring="trop"))


@pytest.mark.skipif(not CPU, reason="crossover constants are per-host; "
                                    "the pinned picks assume CPU")
def test_planner_picks_pallas_above_crossover():
    sp = _bool_plan(5000).strata[0]
    assert sp.runner == "sparse_frontier_pallas", sp.considered
    assert "sparse_frontier_pallas" in sp.considered


@pytest.mark.skipif(not CPU, reason="crossover constants are per-host")
def test_planner_rejects_below_crossover():
    sp = _bool_plan(200).strata[0]
    assert sp.runner != "sparse_frontier_pallas"
    assert "below the fused-kernel crossover" in \
        sp.rejected["sparse_frontier_pallas"]


@pytest.mark.skipif(not CPU, reason="crossover constants are per-host")
def test_planner_rejects_latency_objective():
    sp = _bool_plan(5000, objective="latency").strata[0]
    assert sp.runner != "sparse_frontier_pallas"
    assert "batched-serving backend" in \
        sp.rejected["sparse_frontier_pallas"]


@pytest.mark.skipif(not CPU, reason="crossover constants are per-host")
def test_planner_rejects_semiring_without_measured_win():
    """trop measured slower fused than jnp on CPU — that IS the
    crossover (SpmmKernelModel.host_speedup has no trop entry)."""
    sp = _trop_plan(2000).strata[0]
    assert sp.runner != "sparse_frontier_pallas"
    assert "no measured fused-kernel win" in \
        sp.rejected["sparse_frontier_pallas"]


@pytest.mark.skipif(not CPU, reason="crossover constants are per-host")
def test_planner_pick_flips_with_measured_constants(monkeypatch):
    """The pick is pinned to SpmmKernelModel, not hardcoded: grant trop
    a measured win and it flips in; revoke bool's and it flips out."""
    monkeypatch.setitem(planner.SPMM_COST.host_speedup, "trop", 5.0)
    sp = _trop_plan(2000).strata[0]
    assert sp.runner == "sparse_frontier_pallas", sp.rejected
    monkeypatch.setitem(planner.SPMM_COST.host_speedup, "bool", 0.0)
    sp = _bool_plan(5000).strata[0]
    assert sp.runner != "sparse_frontier_pallas"
    assert "no measured fused-kernel win" in \
        sp.rejected["sparse_frontier_pallas"]


@pytest.mark.parametrize("sr_name", SEMIRINGS)
@pytest.mark.parametrize("transpose", [True, False])
def test_padded_slots_counts_the_geometry(sr_name, transpose):
    """The planner's slot count, taken without building the geometry,
    equals the chunk slots the kernel sweeps."""
    rel = _relation(3000, 3, sr_name, seed=7)
    plan = coo_spmm.plan_geometry(rel, transpose=transpose)
    locs = coo_spmm._chunk_geometry(plan)[3]
    assert coo_spmm.padded_slots(rel, transpose=transpose) \
        == locs.shape[0] * plan.bk


@pytest.mark.skipif(not CPU, reason="crossover constants are per-host")
def test_planner_prices_padded_geometry(monkeypatch):
    """On the kernel path the candidate is priced by its padded slots:
    padding that outweighs the per-slot win is rejected, and so is a
    geometry larger than device memory, each with its reason."""
    monkeypatch.setattr(kops, "_FORCE_INTERPRET", True)
    plan = _bool_plan(5000)
    why = plan.strata[0].rejected["sparse_frontier_pallas"]
    assert "slots for nnz(E)=" in why and "per-slot win" in why
    assert why in planner.explain(plan)
    monkeypatch.setitem(planner.SPMM_COST.host_speedup, "bool", 1e3)
    assert _bool_plan(5000).strata[0].runner == "sparse_frontier_pallas"
    monkeypatch.setattr(planner, "_device_bytes", lambda: 1e5)
    plan = _bool_plan(5000)
    why = plan.strata[0].rejected["sparse_frontier_pallas"]
    assert "geometry does not fit" in why
    assert why in planner.explain(plan)


def test_tpu_never_interprets(monkeypatch):
    """Interpret mode follows from the backend alone: forcing the kernel
    path on a TPU still compiles it."""
    monkeypatch.setattr(kops, "_FORCE_INTERPRET", True)
    assert kops._use_pallas()
    assert kops.pallas_interpret() == CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kops._use_pallas() and not kops.pallas_interpret()


@pytest.mark.skipif(not CPU, reason="crossover constants are per-host")
def test_pallas_plan_answers_match_naive(monkeypatch):
    """End-to-end: the sparse_frontier_pallas plan's answers (and its
    compile_batched unit) are bit-exact vs the jnp runners.  The
    crossover floor is lowered so the cell stays small enough for
    interpret mode, which the forced kernel path runs here in place of
    the host loop."""
    monkeypatch.setattr(planner.SPMM_COST, "min_nnz", 1024.0)
    monkeypatch.setattr(kops, "_FORCE_INTERPRET", True)
    n = 800
    g = datasets.erdos_renyi(n, 3.0, seed=2)
    schema = programs.bm(a=0).original.schema
    db = engine.Database(schema, {"id": n},
                         {"E": g.sparse_adjacency(),
                          "V": jnp.ones((n,), bool)})
    b = programs.bm(a=0)
    plan = planner.plan_program(b.optimized, db, objective="throughput")
    assert plan.strata[0].runner == "sparse_frontier_pallas"
    got, _ = run_program(b.optimized, db, plan=plan)
    ref, _ = run_program(b.optimized, db, mode="seminaive")
    assert np.array_equal(np.asarray(got), np.asarray(ref))
    # the batched serve unit off the same plan
    rel = db.relations["E"].as_jnp()
    init = np.zeros((4, n), bool)
    init[np.arange(4), np.arange(4)] = True
    run = planner.compile_batched(plan, max_iters=10_000)
    x_b, it_b = run(rel, jnp.asarray(init))
    x_r, it_r = sparse_seminaive_fixpoint(rel, jnp.asarray(init),
                                          mode="jit")
    assert np.array_equal(np.asarray(x_b), np.asarray(x_r))
    assert np.array_equal(np.asarray(it_b), np.asarray(it_r))


def test_spmm_exec_backend_resolution(monkeypatch):
    assert planner.spmm_exec_backend("sparse_jit") == "jnp"
    assert planner.spmm_exec_backend("sparse_sharded") == "jnp"
    monkeypatch.setattr(kops, "_FORCE_INTERPRET", True)
    assert planner.spmm_exec_backend("sparse_frontier_pallas") == "pallas"
    if CPU:
        monkeypatch.setattr(kops, "_FORCE_INTERPRET", False)
        assert planner.spmm_exec_backend("sparse_frontier_pallas") \
            == "fused"
