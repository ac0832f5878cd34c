"""Cost-based execution planning: one ``plan → explain → execute`` pipeline.

DESIGN.md §4.  The FGH rewrite produces a *program*; which physical
runner executes each stratum — dense naive, dense GSN
(:func:`repro.core.fixpoint.seminaive_fixpoint`), the sparse jit/frontier
vector runners (:mod:`repro.sparse.fixpoint`), or the vectorized
``x = init ⊕ x ⊗ E`` SpMV/SpMM step (split by :mod:`repro.core.vectorize`)
— and which storage each relation should use, is a classic physical-plan
decision.  It used to be made ad hoc at three sites: ``run_program``'s
mode strings, the serve loop's bespoke vector-form routing, and host-side
``Database.adapt`` calls.  Now :func:`plan_program` makes it once,
:func:`explain` renders it, and :func:`execute_plan` /
:func:`compile_batched` execute it.

Cost model: an analytic O(n²)-vs-O(nnz(E)) × trip-count estimate by
default, or ``cost_model="hlo"`` which stages each candidate's
per-iteration step function and walks its optimized HLO with
:func:`repro.launch.hlo_cost.staged_cost` — the same trip-count-aware
walker the AOT dry-runs (:mod:`repro.launch.dryrun`,
:mod:`repro.launch.datalog_dryrun`) report from.

Storage is folded into planning: the hysteresis thresholds of
:mod:`repro.sparse.adaptive` (via :func:`repro.sparse.adaptive.decide`)
pick a per-relation representation for every binary relation a stratum
reads, replacing host-side ``Database.adapt`` calls between strata.

Plan identity: ``ExecutionPlan.signature`` is a stable hash of the
per-stratum (runner, IDB shapes/semirings, linear-operator signature or
stratum structure, storage decisions) — the serve loop keys its compile
cache on ``(plan.signature, batch_bucket)``.  Staged-executable caching
inside :func:`execute_plan` keys on :func:`db_fingerprint`, a
weakref-token fingerprint of the relation arrays (never raw ``id()``,
which can be recycled after GC and silently serve a stale staged
fixpoint).
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import math
import warnings
import weakref
from typing import Callable, Mapping

import jax
import numpy as np

from repro.core import engine, ir, vectorize
from repro.core import semiring as sr_mod
from repro.sparse import adaptive
from repro.sparse.coo import SparseRelation

#: physical runners, in tie-break preference order (earlier wins ties).
#: "delta_restart" is the incremental-maintenance strategy (DESIGN.md §5):
#: it resumes the previous solution instead of recomputing, so at equal
#: priced cost it can only do less work — hence it leads the order.
#: "synth_maintenance" is its non-monotone sibling (DESIGN.md §11): a
#: CEGIS-verified ⊖/recount rule repairing deletes/weight-increases from
#: the warm solution; it is only *considered* under
#: ``objective="incremental"`` with a non-merge ``delta_op`` and a
#: verified rule already in the maintenance cache.  Both are executed by
#: :func:`repro.incremental.refresh_program` (or the serve loop), never
#: by :func:`execute_plan` (which has no previous solution to restart
#: from).
RUNNERS = ("synth_maintenance", "delta_restart", "sparse_sharded",
           "sparse_frontier_pallas", "sparse_jit", "sparse_frontier",
           "vector_dense", "dense_gsn", "dense_naive", "dense_host")

#: single-device runners that execute the vector equation
#: ``x = init ⊕ x ⊗ E``.  "sparse_frontier_pallas" is the fused-kernel
#: SpMM backend (kernels/coo_spmm.py, DESIGN.md §9): the same staged GSN
#: loop as "sparse_jit" with the gather→⊗→segment-⊕ advance fused into
#: one pass — a Pallas kernel on TPU, bit-packed host rounds for 𝔹 on
#: CPU (see :func:`spmm_exec_backend`).
VECTOR_RUNNERS = ("sparse_jit", "sparse_frontier", "sparse_frontier_pallas",
                  "vector_dense")

#: every vector-equation runner the serve loop can batch — the
#: single-device three plus the graph-axis sharded SpMM loop
#: (:mod:`repro.distributed.datalog`, DESIGN.md §6)
BATCHED_RUNNERS = VECTOR_RUNNERS + ("sparse_sharded",)

#: legacy ``run_program`` mode strings → forced runners; any *other*
#: unknown string keeps the historical "host loop with stats" behaviour
LEGACY_MODES = {"naive": "dense_naive", "seminaive": "dense_gsn",
                "host": "dense_host"}

#: max trip-count the analytic model will predict (deep chains saturate)
_TRIP_CAP = 64

#: staged-executable cache entries kept per Program object
_CACHE_MAX = 512


# --------------------------------------------------------------------------
# Stable relation fingerprints (the plan-cache key fix)
# --------------------------------------------------------------------------

_fp_tokens: dict[int, tuple[int, object]] = {}
_fp_counter = itertools.count()


def _token(obj) -> int:
    """A process-unique token for ``obj`` that is *never* recycled.

    ``id(obj)`` alone is unsafe as a cache key: CPython reuses addresses
    after GC, so a fresh relation array can silently alias a dead one's
    cache entry.  Here the id is only a lookup hint — a weakref callback
    evicts the entry the moment the referent dies, so a recycled id is
    issued a fresh token.  (All our leaf types — numpy arrays, jax
    arrays, :class:`SparseRelation` — support weakrefs; a non-weakrefable
    object gets a fresh token on every call, trading cache hits for
    guaranteed staleness-freedom.)
    """
    key = id(obj)
    ent = _fp_tokens.get(key)
    if ent is not None and ent[1]() is not obj:
        ent = None  # id recycled before the callback ran
    if ent is None:
        tok = next(_fp_counter)

        def _evict(ref, k=key):
            # only evict our own entry — a late callback from the dead
            # object must not pop a fresh entry at the recycled id
            cur = _fp_tokens.get(k)
            if cur is not None and cur[1] is ref:
                _fp_tokens.pop(k, None)

        try:
            ref = weakref.ref(obj, _evict)
        except TypeError:
            # non-weakrefable leaf: no death notification is possible, so
            # never memoize — a fresh token per call can only cause cache
            # misses, never a stale hit on a recycled id
            return tok
        _fp_tokens[key] = (tok, ref)
        return tok
    return ent[0]


def value_fingerprint(v) -> tuple:
    """Stable fingerprint of one stored relation: shape/dtype/semiring
    plus the weakref token of the backing buffer(s)."""
    if isinstance(v, SparseRelation):
        return ("coo", v.shape, v.semiring, _token(v.coords),
                _token(v.values))
    return (_token(v), tuple(getattr(v, "shape", ())),
            str(getattr(v, "dtype", type(v).__name__)))


def db_fingerprint(db: engine.Database, names=None) -> tuple:
    """Fingerprint of (a subset of) a database's relations, plus its sort
    domains — staged fixpoints bake domain sizes into output shapes even
    when no relation array reflects them."""
    if names is None:
        names = db.relations
    return (tuple(sorted(db.domains.items())),
            tuple((n, value_fingerprint(db.relations[n]))
                  for n in sorted(names) if n in db.relations))


# --------------------------------------------------------------------------
# Plan data model
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlanHints:
    """Typed planning hints — the one structured object threaded through
    :func:`plan_program` / :func:`plan_for` / :func:`execute_plan`
    (DESIGN.md §10).

    ``sorts`` maps variable names to sort names, overriding
    ``Program.sort_hints`` (this is what the old loose ``hints`` dicts
    carried; a plain mapping is still accepted everywhere with a
    ``DeprecationWarning``).  ``adaptive=True`` turns on mid-fixpoint
    re-planning in :func:`execute_plan`: chunkable vector strata run
    under :func:`repro.core.runners.adaptive_fixpoint` and may switch
    runners at chunk boundaries.  ``replan`` overrides the default
    :class:`repro.sparse.adaptive.ReplanPolicy` (hysteresis, chunk
    size, switch bounds).
    """

    sorts: Mapping[str, str] = dataclasses.field(default_factory=dict)
    adaptive: bool = False
    replan: object | None = None

    def __post_init__(self):
        for k, v in dict(self.sorts).items():
            if not isinstance(k, str) or not isinstance(v, str):
                raise TypeError(f"PlanHints.sorts maps variable names to "
                                f"sort names, got {k!r}: {v!r}")
        if self.replan is not None and \
                not isinstance(self.replan, adaptive.ReplanPolicy):
            raise TypeError(f"PlanHints.replan must be a ReplanPolicy, "
                            f"got {type(self.replan).__name__}")

    @classmethod
    def of(cls, hints, *, defaults=None) -> "PlanHints":
        """Normalize a caller-supplied ``hints``: ``None`` falls back to
        ``defaults`` (the program's ``sort_hints``), a :class:`PlanHints`
        passes through, and a legacy mapping is wrapped with a
        deprecation warning."""
        if hints is None:
            return cls(sorts=dict(defaults or {}))
        if isinstance(hints, cls):
            return hints
        if isinstance(hints, Mapping):
            warnings.warn("loose hints dicts are deprecated; pass "
                          "planner.PlanHints(sorts={...})",
                          DeprecationWarning, stacklevel=3)
            return cls(sorts=dict(hints))
        raise TypeError(f"hints must be a PlanHints or a mapping, got "
                        f"{type(hints).__name__}")

    def cache_key(self) -> tuple:
        return (tuple(sorted(dict(self.sorts).items())), self.adaptive,
                self.replan)


@dataclasses.dataclass(frozen=True)
class CostEstimate:
    """Per-iteration work × predicted trip count for one candidate."""

    flops_per_iter: float
    bytes_per_iter: float
    trips: int
    source: str = "analytic"  # "analytic" | "hlo"

    @property
    def total(self) -> float:
        return self.flops_per_iter * self.trips


@dataclasses.dataclass
class ShardedCostModel:
    """Measured constants behind the ``sparse_sharded`` candidate
    (DESIGN.md §8, calibrated against ``BENCH_sharded.json``).

    Sharding pays a fixed per-iteration toll — D synchronizing
    collectives plus the exchanged frontier bytes — so it only wins
    once per-device work dwarfs that toll.  ``min_work_per_device`` is
    the measured crossover: below it the partition is *rejected*
    outright (the PR-5 model picked sharding where one device was
    30–50× faster).  Above it, the candidate is priced with its sync
    and byte terms so close calls still compare honestly.  The
    BENCH_sharded.json sweep with the Δ-sparse exchange measures D=8
    already winning ~1.4× at 1.1e5 work/device/iter, so the floor sits
    well under that; toy graphs (≲1e4 work/device) stay single-device.
    Tests monkeypatch the fields to pin either side of the crossover.
    """

    #: (nnz + n)/D per iteration below which sharding cannot recoup its
    #: collective overhead — from the BENCH_sharded.json crossover sweep
    min_work_per_device: float = 2.0e4
    #: flop-equivalent cost of one synchronizing collective per device
    sync_flops_per_device: float = 1.0e4
    #: flop-equivalent cost per exchanged byte
    byte_flops: float = 0.05

    def sync_flops(self, d: int, backend: str) -> float:
        # host-simulated devices share cores: collectives serialize,
        # so the toll grows ~D per participant instead of staying flat
        scale = d if backend == "cpu" else 1
        return self.sync_flops_per_device * d * scale


#: module-level so tests and calibration sweeps can patch it in place
SHARDED_COST = ShardedCostModel()


@dataclasses.dataclass
class SpmmKernelModel:
    """Measured constants behind the ``sparse_frontier_pallas`` candidate
    (DESIGN.md §9, calibrated against ``BENCH_kernels.json``).

    The fused SpMM's win is per-iteration memory traffic, so it is
    priced as the jnp step scaled by the measured per-iteration speedup
    — ``hlo_cost.staged_cost`` prices the jnp step under
    ``cost_model="hlo"`` and the analytic model otherwise; this model
    supplies the scale and the crossover floor on top (the
    ``SHARDED_COST`` pattern).  On CPU the backend is the bit-packed
    host loop, measured 27× per-iteration for 𝔹 at the 50k-vertex
    B=64 serve shape (the 8× default leaves headroom for shallow
    fixpoints, where geometry planning amortizes over fewer rounds);
    f32 lattices (trop/maxplus) measured *slower* fused than the jnp
    scatter loop on CPU, so they get no win and stay on jnp — that IS
    the measured crossover, not a gap.  Tests monkeypatch the fields to
    pin both sides.
    """

    #: nnz(E) below which geometry planning + packing outweigh the
    #: per-iteration win (small graphs converge in ~ms either way)
    min_nnz: float = 4096.0
    #: measured per-iteration speedup of the host fused backend, per
    #: semiring; absent semirings measured no win on CPU
    host_speedup: dict = dataclasses.field(
        default_factory=lambda: {"bool": 8.0})
    #: per-iteration speedup credited to the fused Pallas kernel on TPU
    #: (one HBM pass instead of three for gather/⊗/scatter)
    tpu_speedup: float = 2.0

    def speedup(self, semiring: str, backend: str) -> float:
        """Measured per-iteration win on this platform; ≤ 1 ⇒ no win."""
        if backend == "tpu":
            return self.tpu_speedup
        return float(self.host_speedup.get(semiring, 0.0))


#: module-level so tests and calibration sweeps can patch it in place
SPMM_COST = SpmmKernelModel()


def _device_bytes() -> float | None:
    """Memory of the default device where the backend reports it (TPU);
    ``None`` elsewhere — the host's memory is not the planner's to
    budget."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return float(limit) if limit else None


def spmm_exec_backend(runner: str = "sparse_frontier_pallas") -> str:
    """Resolve a runner's SpMM execution backend on this host.

    The ``sparse_frontier_pallas`` runner compiles the fused Pallas
    kernel on TPU (and under interpret forcing, so CI exercises the
    kernel path) and falls back to the fused host loop elsewhere; every
    other runner keeps the traceable jnp composition.  Serve-side
    kernel caches key on this value.
    """
    if runner != "sparse_frontier_pallas":
        return "jnp"
    from repro.kernels import ops as kops
    if kops._use_pallas():
        return "pallas"
    return "fused"


@dataclasses.dataclass
class StratumPlan:
    """The physical choice for one fixpoint stratum."""

    index: int
    idbs: tuple[str, ...]
    runner: str
    reason: str
    storage: dict[str, str]        # relation → target repr (changes only)
    storage_notes: dict[str, str]  # relation → human-readable decision
    reads: tuple[str, ...]         # relation names this stratum consumes
    cost: CostEstimate | None
    considered: dict[str, CostEstimate]
    rejected: dict[str, str]
    vf: vectorize.VectorForm | None = None
    edges_override: object | None = None
    partition: str | None = None   # sparse_sharded: the graph-axis split
    #: trace of the last *adaptive* execution of this stratum (a
    #: :class:`repro.core.runners.AdaptiveRun`) — populated by
    #: :func:`execute_plan` under ``PlanHints(adaptive=True)`` and
    #: rendered by :func:`explain`; ``None`` until then, so static
    #: plans render byte-identically to the pre-§10 planner
    switch_log: object | None = None


@dataclasses.dataclass
class ExecutionPlan:
    """A fully-decided physical plan for a :class:`~repro.core.program.
    Program` against one database shape."""

    program: str
    objective: str
    mode: str                 # "auto" or the forcing mode string
    strata: list[StratumPlan]
    outputs: tuple[str, ...]
    has_post: bool
    signature: str
    #: the graph mesh this plan was priced against — a jax Mesh with a
    #: "graph" axis (executable), or a plain int D (planning/explain
    #: only; execution resolves a local mesh of that size).  ``None``
    #: plans are single-device and identical to the pre-§6 planner.
    mesh: object | None = None
    #: execute with mid-fixpoint re-planning (from PlanHints.adaptive)
    adaptive: bool = False
    #: the ReplanPolicy to execute under (from PlanHints.replan)
    replan: object | None = None


# --------------------------------------------------------------------------
# Planning
# --------------------------------------------------------------------------


def plan_program(prog, db: engine.Database, hints=None, *,
                 objective: str = "latency", mode: str = "auto",
                 max_iters: int = 10_000, cost_model: str = "analytic",
                 edges=None, adapt_storage: bool = True,
                 require_vector: bool = False,
                 delta_nnz: int | None = None,
                 delta_op: str = "merge",
                 mesh=None) -> ExecutionPlan:
    """Choose a physical runner + storage for every stratum of ``prog``.

    ``objective`` is "latency" (one query; host frontier worklists are in
    play on CPU), "throughput" (batched serving; only staged runners), or
    "incremental" (a warm previous solution exists and ``delta_nnz``
    tuples just changed — the "delta_restart" strategy is priced at
    O(nnz(Δ) · affected-trip-count) against every full-recompute
    candidate, DESIGN.md §5).  ``delta_op`` classifies the update for
    the incremental objective: ``"merge"`` (monotone ⊕, the default)
    keeps delta-restart in play, while ``"delete"``/``"increase"``/
    ``"mixed"`` reject it with a recorded reason and instead consider
    the "synth_maintenance" runner whenever a CEGIS-verified ⊖/recount
    rule for (program signature, semiring, op) is already cached
    (:func:`repro.incremental.maintenance.cached_rule`; planning never
    synthesizes — callers run :func:`repro.incremental.maintenance.
    ensure_rule` first, see DESIGN.md §11).  ``mode`` other than "auto"
    forces a runner on every stratum (legacy ``run_program`` strings
    compile to forced plans).  ``edges`` overrides the extracted linear
    operator of a single-stratum vector program (the serve loop's
    weighted-COO escape hatch).  ``adapt_storage=False`` pins every
    relation to its caller-chosen representation.  ``require_vector=True``
    raises ``ValueError`` with the recorded rejection reason when
    stratum 0 cannot take a vector runner (the serve loop can only batch
    the vector equation).

    ``mesh`` adds the device dimension (DESIGN.md §6): a jax Mesh with a
    ``("graph",)`` axis — or a plain int D for planning-only — makes the
    row-partitioned ``sparse_sharded`` runner a candidate, priced at
    per-shard nnz work plus the per-iteration frontier all-gather, and
    rejected with a recorded reason on single-device meshes or dense
    operators.  ``mesh=None`` plans are byte-identical to before.

    ``hints`` is a :class:`PlanHints` (legacy mappings of sort overrides
    are accepted with a ``DeprecationWarning``); ``PlanHints(
    adaptive=True)`` marks the plan for mid-fixpoint re-planning at
    execution (DESIGN.md §10).
    """
    if objective not in ("latency", "throughput", "incremental"):
        raise ValueError(f"unknown objective {objective!r}")
    ph = PlanHints.of(hints, defaults=prog.sort_hints)
    hints = dict(ph.sorts)
    if mesh is not None:
        from repro.distributed.datalog import mesh_size
        mesh_size(mesh)  # validate early: needs a "graph" axis / D ≥ 1
    forced = None
    if mode != "auto":
        forced = mode if mode in RUNNERS else \
            LEGACY_MODES.get(mode, "dense_host")
        if forced in ("delta_restart", "synth_maintenance"):
            raise ValueError(
                f"{forced} cannot be forced by mode= — it needs a "
                "previous solution; use objective='incremental' and "
                "repro.incremental.refresh_program")
        if forced == "sparse_sharded" and mesh is None:
            raise ValueError(
                "sparse_sharded needs a graph mesh — pass mesh= "
                "(launch.mesh.make_graph_mesh) alongside the forced mode")
    plans = []
    for si, stratum in enumerate(prog.strata):
        plans.append(_plan_stratum(
            prog, stratum, si, db, hints, objective=objective,
            forced=forced, cost_model=cost_model,
            edges=edges if si == 0 else None,
            adapt_storage=adapt_storage and forced is None,
            max_iters=max_iters,
            delta_nnz=delta_nnz if si == 0 else None,
            delta_op=delta_op, mesh=mesh))
    plan = ExecutionPlan(
        prog.name, objective, mode, plans,
        tuple(r.head for r in prog.outputs), prog.post is not None,
        _plan_signature(prog, db, plans), mesh=mesh,
        adaptive=ph.adaptive, replan=ph.replan)
    if require_vector:
        sp = plan.strata[0] if plan.strata else None
        if sp is None or sp.runner not in BATCHED_RUNNERS:
            why = "program has no fixpoint stratum" if sp is None \
                else _vector_rejection(sp.rejected)
            raise ValueError(f"{prog.name}: {why}")
    return plan


def _vector_rejection(rejected: Mapping[str, str]) -> str:
    """The most informative recorded reason why no vector runner was
    feasible — one helper so require_vector and the edges-override guard
    report the same infeasibility identically."""
    return (rejected.get("sparse_jit") or rejected.get("vector_dense")
            or "no vector-form runner is feasible")


def plan_for(prog, db: engine.Database, *, mode: str = "auto",
             max_iters: int = 10_000, objective: str = "latency",
             hints=None) -> ExecutionPlan:
    """Memoized :func:`plan_program` for repeated ``run_program`` calls:
    plans are cached on the Program object, keyed by the database
    fingerprint (stable across GC — see :func:`db_fingerprint`) and the
    normalized :class:`PlanHints`."""
    ph = PlanHints.of(hints, defaults=prog.sort_hints)
    cache = prog.__dict__.setdefault("_plan_cache", {})
    reads: set[str] = set()
    for stratum in prog.strata:
        reads |= _referenced(stratum)
    key = ("plan", mode, objective, max_iters, jax.default_backend(),
           ph.cache_key(), db_fingerprint(db, reads & set(db.relations)))
    plan = _cache_get(cache, key)
    if plan is None:
        plan = cache[key] = plan_program(prog, db, ph, mode=mode,
                                         objective=objective,
                                         max_iters=max_iters)
    return plan


def _cache_get(cache: dict, key):
    """Cache lookup that refreshes recency: the eviction loop in
    :func:`execute_plan` pops insertion-order-oldest entries, so a hit
    must move its entry to the end or steady-state reuse would evict
    exactly the entries being reused."""
    if key in cache:
        cache[key] = cache.pop(key)
        return cache[key]
    return None


def _referenced(stratum) -> set[str]:
    names: set[str] = set()
    exprs = [r.body for r in stratum.rules.values()]
    if stratum.init:
        exprs.extend(stratum.init.values())
    for e in exprs:
        for t in e.terms:
            for a in t.atoms:
                if isinstance(a, ir.RelAtom):
                    names.add(a.name)
    return names


def _edge_rel_name(vf: vectorize.VectorForm) -> str | None:
    """Relation name behind the sparse-preserving fast path of
    :func:`repro.core.vectorize.edge_operator` (the shared
    :func:`repro.core.vectorize.edge_atom` predicate)."""
    a = vectorize.edge_atom(vf)
    return a.name if a is not None else None


def _trip_estimate(n: int, nnz: float, cap: int = _TRIP_CAP) -> int:
    """Heuristic fixpoint depth: ≈ diameter of a random graph with the
    observed average degree, clipped to [3, ``cap``]."""
    deg = nnz / max(n, 1)
    if deg <= 1.0:
        return cap
    return int(min(cap, max(
        3, math.ceil(math.log(max(n, 2)) / math.log(deg)))))


def _term_flops(term: ir.Term, sorts: Mapping[str, str],
                db: engine.Database, planned: Mapping[str, str],
                densities: Mapping[str, float]) -> float:
    """Work of one sum-product term ≈ the broadcast join size, scaled by
    the density of any sparse-stored binary relation in it (the engine's
    SpMV/SpMM path does O(nnz) work instead of O(n²))."""
    vs = sorted(term.vars())
    size = 1.0
    for v in vs:
        size *= float(db.dom(sorts.get(v, "id")))
    scale = 1.0
    for a in term.atoms:
        if (isinstance(a, ir.RelAtom) and planned.get(a.name) == "sparse"
                and a.name in densities):
            scale = min(scale, max(densities[a.name], 1e-12))
    return max(size * scale, 1.0)


def _plan_stratum(prog, stratum, si, db, hints, *, objective, forced,
                  cost_model, edges, adapt_storage, max_iters,
                  delta_nnz=None, delta_op="merge",
                  mesh=None) -> StratumPlan:
    # ``reads`` keeps every referenced relation name — including IDBs of
    # *earlier strata*, which exist only at execution time; the executor
    # fingerprints the input database over the union of all strata's
    # reads, so a later stratum's cache key still varies with the EDBs
    # that feed it.
    reads = tuple(sorted(_referenced(stratum)))
    if forced is not None:
        # a forced runner needs no candidate enumeration — skip density
        # transfers, sort inference, and vector-form splitting (the CEGIS
        # verifier forces "naive" on every candidate × sample db)
        return _forced_stratum_plan(prog, stratum, si, forced, reads,
                                    edges, mesh=mesh)

    # -- storage folding (adaptive density thresholds, DESIGN.md §2/§4) ----
    storage: dict[str, str] = {}
    notes: dict[str, str] = {}
    densities: dict[str, float] = {}
    for name in (n for n in reads if n in db.relations):
        arr = db.relations[name]
        arity = arr.arity if isinstance(arr, SparseRelation) else np.ndim(arr)
        if arity != 2:
            continue  # only binary relations have sparse contraction paths
        d = adaptive.density(arr, db.schema[name].semiring)
        densities[name] = d
        cur = db.storage_of(name)
        target = adaptive.decide(d, cur) if adapt_storage else cur
        if target != cur:
            storage[name] = target
            bound = (f"< {adaptive.SPARSIFY_BELOW:g}" if target == "sparse"
                     else f"> {adaptive.DENSIFY_ABOVE:g}")
            notes[name] = f"{cur}→{target} (density {d:.3g} {bound})"
    planned = {name: storage.get(name, db.storage_of(name))
               for name in reads}

    shapes = {n: tuple(db.dom(s) for s in prog.schema[n].sorts)
              for n in stratum.idbs}
    state = float(sum(float(np.prod(s)) for s in shapes.values()))
    nnz_total = sum(densities[n] *
                    float(np.prod(_rel_shape(db.relations[n])))
                    for n in densities)
    n_dom = int(max((d for s in shapes.values() for d in s), default=1))

    considered: dict[str, CostEstimate] = {}
    rejected: dict[str, str] = {}

    # -- vector-equation feasibility (also pins the trip estimate) ---------
    vf = None
    if len(prog.strata) != 1:
        why = "multi-stratum program (the vector equation covers exactly " \
              "one stratum)"
        for r in VECTOR_RUNNERS:
            rejected[r] = why
    else:
        try:
            vf = vectorize.vector_form(prog)
        except ValueError as e:
            for r in VECTOR_RUNNERS:
                rejected[r] = str(e)
    if vf is not None:
        sr = sr_mod.get(vf.semiring)
        if sr.minus is None:
            why = (f"semiring {vf.semiring} lacks ⊖ — the vector GSN "
                   f"runners need an idempotent lattice")
            for r in VECTOR_RUNNERS:
                rejected[r] = why
            vf = None
    e_nnz = None
    e_rel = None   # the sparse operator itself, when one is stored
    n_vec = n_dom
    if vf is not None:
        n_vec = db.dom(vf.out_sort)
        if edges is not None:
            if isinstance(edges, SparseRelation):
                e_rel = edges
                e_nnz = float(np.asarray(edges.as_np().nnz))
            # a dense override keeps the vector_dense candidate below
        else:
            ename = _edge_rel_name(vf)
            if (ename is not None and ename in db.relations
                    and planned.get(ename) == "sparse"):
                arr = db.relations[ename]
                if isinstance(arr, SparseRelation):
                    e_rel = arr
                    e_nnz = float(np.asarray(arr.as_np().nnz))
                else:
                    e_nnz = densities[ename] * float(
                        np.prod(_rel_shape(arr)))

    # one trip estimate for the whole stratum: every runner executes the
    # same fixpoint, so candidates must never be priced with different
    # iteration counts.  The linear operator's nnz is the best degree
    # signal when available; the all-relations total is the fallback.
    trip_cap = int(max(1, min(_TRIP_CAP, max_iters)))
    if e_nnz is not None:
        trips = _trip_estimate(n_vec, e_nnz, trip_cap)
    else:
        trips = _trip_estimate(n_dom,
                               nnz_total if nnz_total else n_dom * 8.0,
                               trip_cap)

    # -- dense engine candidates ------------------------------------------
    naive_f = state
    gsn_f = state
    for rule in stratum.rules.values():
        sorts = engine.infer_var_sorts(rule.body, prog.schema, hints)
        for t in rule.body.terms:
            f = _term_flops(t, sorts, db, planned, densities)
            naive_f += f
            if any(isinstance(a, ir.RelAtom) and a.name in stratum.rules
                   for a in t.atoms):
                gsn_f += f
    considered["dense_naive"] = CostEstimate(naive_f, 4.0 * naive_f, trips)
    no_minus = [n for n in stratum.idbs
                if sr_mod.get(prog.schema[n].semiring).minus is None]
    if not stratum.is_linear():
        rejected["dense_gsn"] = "non-linear recursion (δF needs a linear " \
                                "program)"
    elif no_minus:
        rejected["dense_gsn"] = (
            f"semiring {prog.schema[no_minus[0]].semiring} lacks ⊖ — GSN "
            f"needs an idempotent lattice")
    else:
        considered["dense_gsn"] = CostEstimate(gsn_f, 4.0 * gsn_f, trips)

    # -- vector-equation candidates ---------------------------------------
    if vf is not None:
        n = n_vec
        if e_nnz is not None:
            # staged loop: a full O(nnz) vspm re-derivation per iteration
            considered["sparse_jit"] = CostEstimate(
                e_nnz + n, 12.0 * e_nnz + 4.0 * n, trips)
            # host worklist: O(nnz) *total* edge expansions (each vertex
            # settles ~once) plus an O(n) Δ-scan per round
            considered["sparse_frontier"] = CostEstimate(
                e_nnz / trips + n, 12.0 * e_nnz / trips + 4.0 * n, trips)
            rejected["vector_dense"] = ("linear operator is sparse — the "
                                        "SpMV/SpMM runners cover it")
        else:
            considered["vector_dense"] = CostEstimate(
                float(n) * n + n, 4.0 * (float(n) * n + n), trips)
            why = "linear operator materializes dense (no sparse binary " \
                  "EDB fast path)"
            rejected["sparse_jit"] = why
            rejected["sparse_frontier"] = why

    # -- graph-axis sharded candidate (DESIGN.md §6/§8) --------------------
    # row-partitioned SpMM under shard_map with the Δ-sparse frontier
    # exchange: per-iteration critical-path work is the balanced shard's
    # frontier-proportional expansion (amortized e_nnz/trips, like the
    # host worklist) plus its O(n/D) carry update — but every iteration
    # also pays D synchronizing collectives and the exchanged bytes.
    # The mesh is an *offer*, not an instruction: below the measured
    # crossover the candidate is rejected so the single-device runners
    # keep regimes they win (the old always-shard policy was the
    # BENCH_sharded.json 30–50× mispick).
    partition = None
    if mesh is not None:
        if vf is None:
            rejected["sparse_sharded"] = _vector_rejection(rejected)
        else:
            from repro.distributed.datalog import mesh_size
            d_ax = mesh_size(mesh)
            nb = -(-n_vec // d_ax)
            if d_ax < 2:
                rejected["sparse_sharded"] = (
                    "graph mesh has a single device — the single-device "
                    "runners cover it")
            elif e_nnz is None:
                rejected["sparse_sharded"] = (
                    "linear operator materializes dense (no sparse "
                    "binary EDB fast path)")
            else:
                cm = SHARDED_COST
                work_dev = (e_nnz + n_vec) / d_ax
                if work_dev < cm.min_work_per_device:
                    rejected["sparse_sharded"] = (
                        f"below the sharding crossover: "
                        f"≈{work_dev:.3g} work/device/iter < "
                        f"{cm.min_work_per_device:g} measured minimum "
                        f"(BENCH_sharded.json) — one device wins")
                else:
                    itemsize = np.dtype(
                        sr_mod.get(vf.semiring).dtype).itemsize
                    dense_b = float(itemsize) * n_vec * (d_ax - 1)
                    delta_b = ((4.0 + itemsize) * (n_vec / trips)
                               * (d_ax - 1))
                    xbytes = min(dense_b, delta_b)
                    sync = cm.sync_flops(d_ax, jax.default_backend())
                    considered["sparse_sharded"] = CostEstimate(
                        e_nnz / trips + n_vec / d_ax + sync
                        + cm.byte_flops * xbytes,
                        12.0 * e_nnz / (trips * d_ax) + xbytes,
                        trips)
                    partition = (
                        f"graph axis D={d_ax} × {nb} dst rows/shard; "
                        f"nnz(E)={int(e_nnz)} "
                        f"(≈{-(-int(e_nnz) // d_ax)}/shard); "
                        f"Δ-exchange ≈{int(xbytes)} B/iter "
                        f"(dense all-gather {int(dense_b)} B)")

    # -- fused-kernel SpMM candidate (DESIGN.md §9) ------------------------
    # the staged GSN loop with the gather→⊗→segment-⊕ advance fused into
    # one pass over edge tiles (kernels/coo_spmm.py).  Offered for
    # batched serving only: the kernel's measured win is amortized
    # across B query lanes, while single-shot latency already belongs to
    # the frontier worklist.  When an offered mesh clears the sharding
    # crossover the partition wins outright — the fused kernel is a
    # single-device backend and has no measured number against D
    # devices.
    if vf is not None:
        if objective != "throughput":
            rejected["sparse_frontier_pallas"] = (
                "fused-kernel SpMM is a batched-serving backend "
                "(objective='throughput') — single-shot latency keeps "
                "the worklist/staged runners")
        elif e_nnz is None:
            rejected["sparse_frontier_pallas"] = (
                "linear operator materializes dense (no sparse binary "
                "EDB fast path)")
        elif "sparse_sharded" in considered:
            rejected["sparse_frontier_pallas"] = (
                "graph-axis sharding clears its crossover — the fused "
                "kernel is single-device and is not priced against a "
                "D-device mesh")
        else:
            cm_k = SPMM_COST
            sp_up = cm_k.speedup(vf.semiring, jax.default_backend())
            if sp_up <= 1.0:
                rejected["sparse_frontier_pallas"] = (
                    f"no measured fused-kernel win for {vf.semiring} on "
                    f"{jax.default_backend()} — the jnp scatter loop is "
                    f"already bandwidth-bound (BENCH_kernels.json)")
            elif e_nnz < cm_k.min_nnz:
                rejected["sparse_frontier_pallas"] = (
                    f"below the fused-kernel crossover: "
                    f"nnz(E)={int(e_nnz)} < {cm_k.min_nnz:g} measured "
                    f"minimum (BENCH_kernels.json) — geometry planning "
                    f"outweighs the per-iteration win")
            else:
                # the Pallas kernel sweeps padded edge tiles, not edges:
                # price its real slot count (the host executors sweep
                # the bare dst-sorted edges)
                slots = e_nnz
                if e_rel is not None and spmm_exec_backend() == "pallas":
                    from repro.kernels import coo_spmm
                    slots = float(coo_spmm.padded_slots(e_rel))
                hbm = _device_bytes()
                if hbm is not None and 12.0 * slots > hbm:
                    rejected["sparse_frontier_pallas"] = (
                        f"padded edge-tile geometry does not fit: "
                        f"{int(slots)} slots ({slots / e_nnz:.1f}/edge) × "
                        f"12 B > {hbm / 2**30:.1f} GiB of device memory")
                elif sp_up * e_nnz / slots <= 1.0:
                    rejected["sparse_frontier_pallas"] = (
                        f"padded edge-tile geometry: {int(slots)} slots "
                        f"for nnz(E)={int(e_nnz)} "
                        f"({slots / e_nnz:.1f}/edge) outweigh the "
                        f"kernel's {sp_up:g}× per-slot win")
                else:
                    considered["sparse_frontier_pallas"] = CostEstimate(
                        (slots + n_vec) / sp_up + n_vec,
                        (12.0 * slots + 4.0 * n_vec) / sp_up, trips)

    # the host worklist only pays off for single-shot latency on a CPU
    # host; batched serving and accelerators want the staged SpMM loop
    frontier_ok = (objective in ("latency", "incremental")
                   and jax.default_backend() == "cpu")
    if "sparse_frontier" in considered and not frontier_ok:
        rejected["sparse_frontier"] = ("host worklist loses to the staged "
                                       "while_loop off-CPU / for batches")
        del considered["sparse_frontier"]
    if objective == "throughput" and \
            any(r in considered for r in VECTOR_RUNNERS):
        for r in ("dense_naive", "dense_gsn"):
            if r in considered:
                rejected[r] = ("not batchable — throughput serving packs "
                               "sources into one vector fixpoint")
                del considered[r]
    if edges is not None:
        # the caller supplied the linear operator; only the vector
        # runners consult it — a dense engine pick would silently run
        # over the database's own relations instead
        for r in ("dense_naive", "dense_gsn"):
            if r in considered:
                rejected[r] = ("edges override requires a vector runner "
                               "(the engine paths read the stored "
                               "relations, not the override)")
                del considered[r]
        if not considered:
            raise ValueError(f"{prog.name}: edges override cannot be "
                             f"honored: {_vector_rejection(rejected)}")

    # -- incremental maintenance: delta-restart / synth_maintenance --------
    # priced at O(nnz(Δ) · affected-trip-count): the warm repair seeds
    # its frontier from the nnz(Δ) touched edges, and per round the
    # affected region grows by ~the average degree, never beyond nnz(E)
    # (full-recompute per-round work).  Only offered under
    # objective="incremental" so latency/throughput plans are unchanged.
    # Monotone ⊕-merges take "delta_restart" (DESIGN.md §5); deletes and
    # weight increases void its pre-fixpoint property and instead take
    # "synth_maintenance" — but only when a CEGIS-verified ⊖/recount
    # rule is already cached for (signature, semiring, op); planning has
    # no side effects, so it never synthesizes one (DESIGN.md §11).
    synth_rule = None
    if objective == "incremental":
        if delta_nnz is None:
            rejected["delta_restart"] = (
                "no update delta recorded — pass delta_nnz "
                "(repro.incremental.refresh_program does)")
            rejected["synth_maintenance"] = rejected["delta_restart"]
        elif vf is None:
            rejected["delta_restart"] = _vector_rejection(rejected)
            rejected["synth_maintenance"] = rejected["delta_restart"]
        elif e_nnz is None:
            rejected["delta_restart"] = (
                "linear operator materializes dense — delta seeding "
                "needs the sparse fast path")
            rejected["synth_maintenance"] = rejected["delta_restart"]
        elif delta_op == "merge":
            deg = max(1.0, e_nnz / max(n_vec, 1))
            affected = min(float(e_nnz), float(delta_nnz) * deg)
            considered["delta_restart"] = CostEstimate(
                affected + 1.0, 12.0 * affected, trips)
            rejected["synth_maintenance"] = (
                "update is a monotone ⊕-merge — delta-restart needs no "
                "synthesized ⊖/recount rule")
        else:
            rejected["delta_restart"] = (
                f"{delta_op} is non-monotone (not a ⊕-merge) — the old "
                f"solution is no pre-fixpoint of the new operator and a "
                f"warm restart could over-derive (DESIGN.md §11)")
            from repro.incremental import maintenance as _mt
            rule = _mt.cached_rule(vf.signature, vf.semiring, delta_op)
            if rule is None:
                rejected["synth_maintenance"] = (
                    f"no maintenance rule cached for ({vf.semiring}, "
                    f"{delta_op}) — run repro.incremental.maintenance."
                    f"ensure_rule first")
            elif not rule.verified:
                rejected["synth_maintenance"] = (
                    f"rule synthesis failed: {rule.reason}")
            else:
                synth_rule = rule
                # seeds ≤ nnz(Δ); the tight cone grows by ~deg per hop
                # and its in-edge recount re-reads each cone vertex's
                # in-adjacency once — a constant factor over the
                # delta-restart frontier estimate
                deg = max(1.0, e_nnz / max(n_vec, 1))
                affected = min(float(e_nnz), float(delta_nnz) * deg)
                considered["synth_maintenance"] = CostEstimate(
                    2.0 * affected + 1.0, 16.0 * affected, trips)

    if cost_model == "hlo":
        considered = _hlo_costs(considered, prog, stratum, db, hints, vf,
                                edges, trips, storage)

    # -- selection ---------------------------------------------------------
    pref = list(RUNNERS)
    if frontier_ok:
        pref.remove("sparse_frontier")
        pref.insert(0, "sparse_frontier")
    runner = min(considered,
                 key=lambda k: (considered[k].total, pref.index(k)))
    cost = considered[runner]
    reason = (f"min est. total flops among "
              f"{len(considered)} feasible candidates")
    if runner == "sparse_frontier":
        reason += " (cpu host ⇒ frontier worklist)"
    if runner == "delta_restart":
        reason += (f" (warm restart: nnz(Δ)={int(delta_nnz)} seeds the "
                   f"frontier)")
    if runner == "synth_maintenance":
        reason += (f" (synthesized rule {synth_rule.name} repairs the "
                   f"{delta_op} in-place: {synth_rule.reason})")
    return StratumPlan(si, tuple(stratum.idbs), runner, reason, storage,
                       notes, reads, cost, considered, rejected, vf, edges,
                       partition if runner == "sparse_sharded" else None)


def _forced_stratum_plan(prog, stratum, si, forced, reads, edges, *,
                         mesh=None) -> StratumPlan:
    """Legacy-mode plans: the runner is predetermined, storage stays as
    the caller chose it, no candidates are priced.  Infeasibility (e.g.
    forcing GSN on a non-linear stratum) surfaces at execution time with
    the historical error, exactly as the pre-planner code did."""
    vf = None
    partition = None
    if forced in BATCHED_RUNNERS:
        if len(prog.strata) != 1:
            raise ValueError(
                f"{prog.name}: cannot force runner {forced!r}: "
                f"multi-stratum program")
        try:
            vf = vectorize.vector_form(prog)
        except ValueError as e:
            raise ValueError(
                f"{prog.name}: cannot force runner {forced!r}: {e}")
        if forced == "sparse_sharded":
            from repro.distributed.datalog import mesh_size
            partition = f"graph axis D={mesh_size(mesh)} (forced)"
    elif edges is not None:
        raise ValueError(
            f"{prog.name}: edges override cannot be honored by forced "
            f"runner {forced!r} — the dense engine paths read the stored "
            f"relations, not the override")
    return StratumPlan(si, tuple(stratum.idbs), forced,
                       f"forced by mode={forced!r}", {}, {}, reads,
                       None, {}, {}, vf, edges, partition)


def _rel_shape(arr):
    return arr.shape if isinstance(arr, SparseRelation) else \
        np.shape(arr)


def _hlo_costs(considered, prog, stratum, db, hints, vf, edges, trips,
               storage):
    """Re-price each feasible candidate by staging its per-iteration step
    and walking the optimized HLO (:func:`repro.launch.hlo_cost.
    staged_cost`).  Falls back to the analytic estimate per candidate."""
    from repro.core import program as prog_mod
    from repro.launch import hlo_cost
    out = dict(considered)
    db2 = db
    for name, target in storage.items():
        db2 = db2.with_storage(name, target)

    def price(runner):
        if runner in ("dense_naive", "dense_gsn"):
            ico = (prog_mod.make_ico(stratum, db2, hints)
                   if runner == "dense_naive"
                   else prog_mod.make_delta_ico(stratum, db2, hints))
            x0 = prog_mod.zero_state(stratum, db2)
            c = hlo_cost.staged_cost(ico, x0)
        elif runner in ("sparse_jit", "sparse_frontier"):
            from repro.sparse import contract
            e = _materialize_edges(vf, db2, hints, override=edges)
            sr = sr_mod.get(vf.semiring)
            d0 = sr.zeros((db2.dom(vf.out_sort),))
            c = hlo_cost.staged_cost(
                lambda d: contract.vspm(d, e), d0)
        else:  # vector_dense
            from repro.kernels import ops as kops
            e = _materialize_edges(vf, db2, hints, override=edges,
                                   densify=True)
            sr = sr_mod.get(vf.semiring)
            d0 = sr.zeros((1, db2.dom(vf.out_sort)))
            c = hlo_cost.staged_cost(
                lambda d: kops.semiring_matmul(sr, d, e), d0)
        return CostEstimate(max(c.flops, 1.0), c.bytes, trips, "hlo")

    for runner in list(out):
        if runner in ("delta_restart", "synth_maintenance",
                      "sparse_sharded", "sparse_frontier_pallas"):
            # none has a single-device staged step to walk (the sharded
            # per-iteration HLO is per-shard; the fused kernel's
            # geometry is host-planned) — analytic stands, except the
            # fused kernel which re-derives from the walked jnp step
            continue
        try:
            out[runner] = price(runner)
        except Exception:  # noqa: BLE001 — keep the analytic estimate
            pass
    if "sparse_frontier_pallas" in out:
        # price the fused kernel as the hlo-walked jnp step scaled by
        # its measured per-iteration win (SPMM_COST), keeping the two
        # candidates on the same footing under cost_model="hlo"
        base = out.get("sparse_jit")
        if base is not None and base.source == "hlo":
            s = max(SPMM_COST.speedup(vf.semiring,
                                      jax.default_backend()), 1.0)
            out["sparse_frontier_pallas"] = CostEstimate(
                base.flops_per_iter / s, base.bytes_per_iter / s,
                trips, "hlo")
    return out


def _plan_signature(prog, db, plans) -> str:
    parts = []
    for sp, stratum in zip(plans, prog.strata):
        shapes = tuple((n, prog.schema[n].semiring,
                        tuple(db.dom(s) for s in prog.schema[n].sorts))
                       for n in sp.idbs)
        core = sp.vf.signature if sp.vf is not None else \
            _stratum_hash(stratum)
        parts.append((sp.runner, shapes, core,
                      tuple(sorted(sp.storage.items()))))
    payload = repr((tuple(r.head for r in prog.outputs), parts))
    return hashlib.sha1(payload.encode()).hexdigest()[:16]


def _stratum_hash(stratum) -> str:
    payload = repr(sorted((n, repr(r.body))
                          for n, r in stratum.rules.items()))
    if stratum.init:
        payload += repr(sorted((n, repr(e))
                               for n, e in stratum.init.items()))
    return hashlib.sha1(payload.encode()).hexdigest()[:16]


# --------------------------------------------------------------------------
# Explain
# --------------------------------------------------------------------------


def explain(plan: ExecutionPlan) -> str:
    """Stable, golden-testable rendering of an :class:`ExecutionPlan`."""
    lines = [f"plan {plan.program}  mode={plan.mode}  "
             f"objective={plan.objective}  signature={plan.signature}"]
    for sp in plan.strata:
        lines.append(f"  stratum {sp.index}  runner={sp.runner}  "
                     f"idbs={','.join(sp.idbs)}")
        lines.append(f"    reason      {sp.reason}")
        if sp.partition is not None:
            lines.append(f"    partition   {sp.partition}")
        for name in sorted(sp.storage):
            lines.append(f"    storage     {name}: {sp.storage_notes[name]}")
        if sp.cost is not None:
            c = sp.cost
            lines.append(f"    cost        {c.flops_per_iter:.3g} flops/iter"
                         f" × {c.trips} iters  [{c.source}]")
        if sp.considered:
            body = "  ".join(
                f"{k}={v.total:.3g}" for k, v in
                sorted(sp.considered.items(),
                       key=lambda kv: (kv[1].total, kv[0])))
            lines.append(f"    considered  {body}")
        for k in sorted(sp.rejected):
            lines.append(f"    rejected    {k}: {sp.rejected[k]}")
        if sp.switch_log is not None:
            # only present after an adaptive execution (DESIGN.md §10);
            # plans that never executed adaptively render byte-
            # identically to the static planner (golden tests)
            t = sp.switch_log
            lines.append(
                f"    adaptive    {len(t.chunks)} chunks × "
                f"{t.policy.chunk_iters} iters, {len(t.switches)} "
                f"switches, finished on {t.final_runner}")
            for ev in t.switches:
                lines.append(
                    f"    switch      chunk {ev.chunk} @ iter "
                    f"{ev.iteration}: {ev.from_runner} → {ev.to_runner}"
                    f"  (frontier nnz={ev.frontier_nnz}, density="
                    f"{ev.density:.3g}, est {ev.est_from:.3g} → "
                    f"{ev.est_to:.3g} ns/iter)")
    outs = " ← ".join(plan.outputs) if plan.outputs else "(fixpoint state)"
    post = "  + host post-epilogue" if plan.has_post else ""
    lines.append(f"  outputs    {outs}{post}")
    return "\n".join(lines)


# --------------------------------------------------------------------------
# Execution
# --------------------------------------------------------------------------


def execute_plan(plan: ExecutionPlan, prog, db: engine.Database, *,
                 max_iters: int = 10_000, hints=None):
    """Run ``prog`` under ``plan``; returns ``(answer, RunStats)``.

    Staged executables, initial states, storage conversions, and
    materialized linear operators are cached on the Program object keyed
    by stable database fingerprints, so a cache hit skips `make_ico` /
    `init_state` / `edge_operator` construction entirely.

    ``hints`` (a :class:`PlanHints`; legacy mappings warn) defaults to
    the program's own sort hints.  Adaptive re-planning runs when either
    the plan or the hints asks for it: chunkable vector strata execute
    via :func:`repro.core.runners.adaptive_fixpoint`, their switch
    history lands on ``StratumPlan.switch_log``, and ``explain(plan)``
    renders it afterwards.
    """
    from repro.core import program as prog_mod
    ph = PlanHints.of(hints, defaults=prog.sort_hints)
    hints = dict(ph.sorts)
    adaptive_exec = bool(plan.adaptive or ph.adaptive)
    replan = ph.replan if ph.replan is not None else plan.replan
    cache = prog.__dict__.setdefault("_plan_cache", {})
    iters_log: list[int] = []
    # one fingerprint of the *input* database anchors every stratum's
    # staged-cache key: stratum outputs are deterministic functions of
    # the EDBs, so later strata reuse their staged closures across runs
    # even though each run materializes fresh intermediate arrays (keying
    # on those would make every later stratum a guaranteed cache miss)
    all_reads: set[str] = set()
    for sp in plan.strata:
        all_reads |= set(sp.reads)
    base_fp = db_fingerprint(db, all_reads)
    cur_db = db
    for sp, stratum in zip(plan.strata, prog.strata):
        cur_db = _apply_storage(sp, cur_db, cache)
        state, iters = _run_stratum(sp, stratum, prog, cur_db, hints,
                                    cache, max_iters, base_fp,
                                    mesh=plan.mesh,
                                    adaptive_exec=adaptive_exec,
                                    replan=replan)
        iters_log.append(int(iters))
        cur_db = cur_db.with_relations(state)
    out = None
    for rule in prog.outputs:
        out = engine.eval_ssp(rule.body, cur_db, hints)
        cur_db = cur_db.with_relations({rule.head: out})
    if prog.post is not None:
        out = prog.post(out, cur_db)
    while len(cache) > _CACHE_MAX:
        cache.pop(next(iter(cache)))
    return out, prog_mod.RunStats(iters_log, plan.mode, plan)


def _apply_storage(sp: StratumPlan, db: engine.Database, cache):
    """Apply the plan's per-relation storage decisions, memoizing each
    converted array so repeated executions reuse one stable object (and
    therefore one stable fingerprint)."""
    for name, target in sp.storage.items():
        arr = db.relations.get(name)
        if arr is None or db.storage_of(name) == target:
            continue
        key = ("storage", name, target, value_fingerprint(arr))
        conv = _cache_get(cache, key)
        if conv is None:
            conv = db.with_storage(name, target).relations[name]
            cache[key] = conv
        db = db.with_relations({name: conv})
    return db


def _materialize_edges(vf, db, hints, *, override=None, densify=False):
    """The linear operator E, cast into the equation's semiring; sparse
    operators land as jnp COO ready for the SpMV/SpMM runners."""
    e = override if override is not None else \
        vectorize.edge_operator(vf, db, hints)
    if isinstance(e, SparseRelation):
        e = vectorize._sparse_into_semiring(e, vf.semiring)
        e = e.to_dense() if densify else e.as_jnp()
    return e


def _mesh_key(mesh):
    """Hashable identity of a (graph) mesh for the staged-runner cache:
    axis layout plus the concrete device ids (an int-D planning mesh
    resolves to the local devices at execution)."""
    from jax.sharding import Mesh
    if isinstance(mesh, Mesh):
        return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
                tuple(d.id for d in mesh.devices.flat))
    return int(mesh)


def exec_mesh(plan: ExecutionPlan):
    """The concrete Mesh a ``sparse_sharded`` plan executes on: the
    plan's own Mesh, or — when planning used a plain int D — a local
    graph mesh of that size (needs ≥ D local devices)."""
    from jax.sharding import Mesh
    if isinstance(plan.mesh, Mesh):
        return plan.mesh
    if plan.mesh is None:
        raise ValueError(f"{plan.program}: sparse_sharded plan has no "
                         f"mesh — re-plan with mesh=")
    from repro.launch.mesh import make_graph_mesh
    return make_graph_mesh(int(plan.mesh))


def _resolve_mesh(mesh, *, required: bool):
    """Concrete Mesh for execution: pass a Mesh through, resolve a plain
    int D against the local devices.  ``required=False`` (the adaptive
    candidate set on a non-sharded plan) tolerates unresolvable meshes —
    the sharded candidate just drops out."""
    if mesh is None:
        return None
    from jax.sharding import Mesh
    if isinstance(mesh, Mesh):
        return mesh
    from repro.launch.mesh import make_graph_mesh
    try:
        return make_graph_mesh(int(mesh))
    except Exception:
        if required:
            raise
        return None


def _run_stratum(sp, stratum, prog, cur_db, hints, cache, max_iters,
                 base_fp, *, mesh=None, adaptive_exec=False, replan=None):
    from repro.core import runners as runners_mod

    if sp.runner == "delta_restart":
        raise ValueError(
            f"{prog.name}: delta_restart plans carry no previous "
            f"solution to restart from — execute them via "
            f"repro.incremental.refresh_program")
    runner = runners_mod.get(sp.runner)
    key = (sp.index, sp.runner, max_iters, base_fp,
           tuple(sorted(sp.storage.items())),
           None if sp.edges_override is None
           else value_fingerprint(sp.edges_override),
           None if mesh is None else _mesh_key(mesh))
    ent = _cache_get(cache, key)

    if sp.runner in BATCHED_RUNNERS:
        if ent is None:
            vf = sp.vf
            edges = _materialize_edges(
                vf, cur_db, hints, override=sp.edges_override,
                densify=sp.runner == "vector_dense")
            if sp.runner != "vector_dense" and \
                    not isinstance(edges, SparseRelation):
                edges = SparseRelation.from_dense(
                    np.asarray(edges), vf.semiring).as_jnp()
            init = vectorize.init_vector(vf, cur_db, hints)
            m = _resolve_mesh(mesh,
                              required=sp.runner == "sparse_sharded")
            ctx = runners_mod.make_context(edges, init, vf.semiring,
                                           max_iters, mesh=m)
            ent = (runner.full_fn(ctx), runner.operand(ctx), ctx)
            cache[key] = ent
        fn, operand, ctx = ent
        if adaptive_exec and runner.chunkable:
            x, iters, trace = runners_mod.adaptive_fixpoint(
                ctx, start=sp.runner, candidates=tuple(sp.considered),
                policy=replan)
            sp.switch_log = trace
        else:
            x, iters = fn(operand, ctx.init)
        return {sp.idbs[0]: x}, int(np.asarray(iters))

    if ent is None:
        ent = runner.stratum_fn(stratum, cur_db, hints, max_iters)
        cache[key] = ent
    fn, x0 = ent
    x, iters = fn(x0)
    return x, int(np.asarray(iters))


# --------------------------------------------------------------------------
# Batched serving hooks (the serve loop's side of the pipeline)
# --------------------------------------------------------------------------


def materialize_edges(plan: ExecutionPlan, db: engine.Database,
                      hints=None, *, override=None):
    """The linear operator for stratum 0, ready for
    :func:`compile_batched` (sparse COO on device, or a dense matrix)."""
    sp = plan.strata[0]
    return _materialize_edges(sp.vf, db, hints,
                              override=override
                              if override is not None
                              else sp.edges_override,
                              densify=sp.runner == "vector_dense")


def source_init(plan: ExecutionPlan, prog, db: engine.Database, *,
                hints=None, backend: str = "jnp"):
    """Vector-form a per-source program, verify it kept the plan's linear
    operator, and evaluate its O(n) init terms."""
    vf = vectorize.vector_form(prog)
    base = plan.strata[0].vf
    if vf.signature != base.signature:
        raise ValueError(
            f"{plan.program}: source program changed the linear operator "
            f"({vf.signature} != {base.signature}) — sources must only "
            f"move the init term")
    return vectorize.init_vector(vf, db, hints, backend=backend)


def compile_batched(plan: ExecutionPlan, *,
                    max_iters: int = 10_000) -> Callable:
    """A jitted ``run(edges, init)`` over a ``(B, n)`` init pack for
    stratum 0's runner — the serve loop's compiled unit, cached by the
    caller under ``(plan.signature, B-bucket)``."""
    from repro.core import runners as runners_mod

    sp = plan.strata[0]
    if sp.runner not in BATCHED_RUNNERS:
        raise ValueError(f"{plan.program}: runner {sp.runner!r} has no "
                         f"batched form")
    return runners_mod.get(sp.runner).batched_fn(plan, max_iters)
