"""Registered Runner protocol + the adaptive re-planning executor
(DESIGN.md §10).

The planner (DESIGN.md §4) picks a physical runner per stratum; until
this module, executing that choice was a string-keyed if/elif ladder in
``planner._run_stratum`` and ``planner.compile_batched``.  Now every
physical runner is a registered :class:`Runner`:

* ``full_fn(ctx)`` — the static path: a ``fn(edges, init)`` closure with
  exactly the wrapping the old ladder used (outer ``jax.jit`` for the
  staged runners, un-jitted for the host worklist and the fused backend
  whose geometry planning needs concrete buffers);
* ``run_chunk(ctx, state, budget) → (state, stats)`` — advance a
  :class:`~repro.sparse.fixpoint.FixpointState` by at most ``budget``
  GSN rounds and report the chunk-boundary
  :class:`~repro.sparse.fixpoint.FrontierStats`;
* ``estimate(ctx, state) → CostEstimate`` — re-price the runner's *next
  round* from the observed frontier
  (:data:`repro.sparse.adaptive.ADAPTIVE_COST`);
* ``finalize(ctx, state)`` — extract ``(x*, iters)`` from the carry.

Because every runner shares the GSN round body (DESIGN.md §2/§6/§9),
the carry is a common currency: :func:`adaptive_fixpoint` executes in
bounded chunks and — under a :class:`~repro.sparse.adaptive.
ReplanPolicy` — hands the state to whichever runner prices cheapest for
the *remaining* fixpoint, bit-exact with any static plan.  That is the
mid-fixpoint adaptive re-planning of Herlihy et al. (PAPERS.md): the
frontier worklist wins while Δ is a handful of vertices, the staged
O(nnz) runners win when it explodes, and real workloads cross that
boundary mid-run.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core import semiring as sr_mod
from repro.sparse import adaptive
from repro.sparse import fixpoint as fx
from repro.sparse.coo import SparseRelation


@dataclasses.dataclass
class RunnerContext:
    """Everything a runner needs to execute one vector-form stratum:
    the materialized linear operator, the init vector, and the memo dict
    (``extras``) where runners stash prepared operands and compiled
    chunk closures — cached alongside the plan so repeat executions
    re-enter compiled code."""

    edges: object            # SparseRelation (jnp COO) or dense matrix
    init: object             # (n,) or (B, n)
    semiring: str
    max_iters: int
    n: int
    e_nnz: int
    mesh: object = None      # concrete graph Mesh (sharded candidate)
    extras: dict = dataclasses.field(default_factory=dict)

    @property
    def batch(self) -> int:
        return int(np.shape(self.init)[0]) if np.ndim(self.init) == 2 \
            else 1


def make_context(edges, init, semiring: str, max_iters: int, *,
                 mesh=None) -> RunnerContext:
    if isinstance(edges, SparseRelation):
        n, e_nnz = int(edges.shape[1]), int(edges.nnz)
    else:
        srn = sr_mod.get(semiring, lib="np")
        arr = np.asarray(edges)
        n, e_nnz = int(arr.shape[1]), int((arr != srn.zero).sum())
    return RunnerContext(edges, init, semiring, max_iters, n, e_nnz,
                         mesh=mesh)


class Runner:
    """One physical fixpoint runner (registered; see module docstring).

    ``vector`` runners execute the vector equation ``x = init ⊕ x ⊗ E``
    from a :class:`RunnerContext`; non-vector (dense engine) runners
    execute a whole stratum via ``stratum_fn``.  ``chunkable`` runners
    additionally support the bounded-chunk protocol and are adaptive-
    executor candidates.
    """

    name: str = ""
    vector: bool = True
    chunkable: bool = False

    def feasible(self, ctx: RunnerContext) -> bool:
        return True

    def operand(self, ctx: RunnerContext):
        """The runner-specific form of the linear operator (sharded,
        densified, ...), memoized on ``ctx.extras``."""
        return ctx.edges

    def full_fn(self, ctx: RunnerContext):
        """The static path: ``fn(operand, init) → (x*, iters)``."""
        raise NotImplementedError(self.name)

    def run_chunk(self, ctx: RunnerContext, state: fx.FixpointState,
                  budget: int):
        raise NotImplementedError(f"runner {self.name} is not chunkable")

    def estimate(self, ctx: RunnerContext,
                 state: fx.FixpointState):
        """Price this runner's next GSN round from the chunk-boundary
        frontier observation (ns; trips cancel across candidates)."""
        from repro.core import planner
        ns = adaptive.ADAPTIVE_COST.round_ns(
            self.name, n=ctx.n, e_nnz=ctx.e_nnz, batch=state.batch,
            frontier_nnz=state.frontier_nnz(),
            live_rows=state.live_rows(), semiring=ctx.semiring,
            fused_speedup=planner.SPMM_COST.speedup(
                ctx.semiring, jax.default_backend()),
            mesh_d=_mesh_d(ctx.mesh))
        return planner.CostEstimate(ns, 0.0, 1, "adaptive")

    def finalize(self, ctx: RunnerContext, state: fx.FixpointState):
        return state.solution()

    def stratum_fn(self, stratum, cur_db, hints, max_iters: int):
        """Non-vector runners: ``(fn, x0)`` executing a whole stratum."""
        raise NotImplementedError(self.name)

    def batched_fn(self, plan, max_iters: int):
        """The :func:`repro.core.planner.compile_batched` body:
        ``run(edges, init)`` over a ``(B, n)`` init pack — jitted here
        unless the runner manages its own compiled closures."""
        raise NotImplementedError(self.name)

    def serve_chunk_fn(self, chunk_iters: int):
        """The serve scheduler's compiled unit: ``(e, y, d, it) →
        (y, d, it)`` advancing the slot-pool carry by ``chunk_iters``
        rounds (:mod:`repro.serve.slots`); named, so a device trace
        calls its program ``jit_fixpoint_chunk``."""
        return fx.CompiledChunk(chunk_iters)


def _mesh_d(mesh) -> int:
    if mesh is None:
        return 1
    from repro.distributed.datalog import mesh_size
    return mesh_size(mesh)


RUNNER_REGISTRY: dict[str, Runner] = {}


def register(runner_cls):
    r = runner_cls()
    RUNNER_REGISTRY[r.name] = r
    return runner_cls


def get(name: str) -> Runner:
    r = RUNNER_REGISTRY.get(name)
    if r is None:
        raise KeyError(f"no registered runner {name!r}; have "
                       f"{sorted(RUNNER_REGISTRY)}")
    return r


# --------------------------------------------------------------------------
# Vector-equation runners
# --------------------------------------------------------------------------


class _SparseRunner(Runner):
    def feasible(self, ctx: RunnerContext) -> bool:
        return isinstance(ctx.edges, SparseRelation)

    def batched_fn(self, plan, max_iters):
        # the batched serve form of both the staged and the frontier
        # runner is the staged loop (one SpMM per round); the frontier
        # representation is per-source and cannot batch
        return jax.jit(lambda e, i: fx.fixpoint(e, i, mode="jit",
                                                max_iters=max_iters))


@register
class FrontierRunner(_SparseRunner):
    """Host worklist rounds: per-round work tracks the live frontier."""

    name = "sparse_frontier"
    chunkable = True

    def full_fn(self, ctx):
        mi = ctx.max_iters
        return lambda e, i: fx.fixpoint(e, i, mode="frontier",
                                        max_iters=mi)

    def run_chunk(self, ctx, state, budget):
        st = fx.fixpoint(ctx.edges, state=state, budget=budget,
                         mode="frontier")
        return st, st.stats()


@register
class JitRunner(_SparseRunner):
    """Staged ``lax.while_loop``: O(nnz(E)) per round, density-blind."""

    name = "sparse_jit"
    chunkable = True
    backend = "jnp"

    def full_fn(self, ctx):
        mi = ctx.max_iters
        return jax.jit(lambda e, i: fx.fixpoint(e, i, mode="jit",
                                                max_iters=mi))

    def run_chunk(self, ctx, state, budget):
        # memoize a jitted chunk per budget so repeat chunks (and the
        # serve loop) re-enter compiled code instead of re-tracing the
        # while_loop; the pallas/fused backends memoize on the SpMM plan
        key = ("chunk", self.name, budget)
        fn = ctx.extras.get(key)
        if fn is None:
            fn = ctx.extras[key] = fx.CompiledChunk(budget)
        ej = ctx.extras.get("jnp_edges")
        if ej is None:
            ej = ctx.extras["jnp_edges"] = ctx.edges.as_jnp()
        y, d, it = fn(ej, np.asarray(state.y), np.asarray(state.delta),
                      np.asarray(state.iters, np.int32))
        st = fx.FixpointState(y, d, it, state.semiring, state.batched)
        return st, st.stats()


@register
class PallasRunner(_SparseRunner):
    """The staged loop with the fused SpMM advance (DESIGN.md §9):
    Pallas kernel on TPU, bit-packed host rounds for 𝔹 on CPU."""

    name = "sparse_frontier_pallas"
    chunkable = True

    def _backend(self) -> str:
        from repro.core import planner
        return planner.spmm_exec_backend(self.name)

    def full_fn(self, ctx):
        # no outer jax.jit: the fused backend plans its edge-tile
        # geometry on the host (needs concrete buffers) and memoizes its
        # own compiled closures per operator
        mi, be = ctx.max_iters, self._backend()
        return lambda e, i: fx.fixpoint(e, i, mode="jit", backend=be,
                                        max_iters=mi)

    def run_chunk(self, ctx, state, budget):
        st = fx.fixpoint(ctx.edges, state=state, budget=budget,
                         backend=self._backend())
        return st, st.stats()

    def batched_fn(self, plan, max_iters):
        # returned un-jitted: the fused backend needs concrete edge
        # buffers for host geometry planning and carries its own
        # per-operator compiled closures (plan.jit_cache), so the serve
        # loop still re-enters compiled code on every call
        be = self._backend()
        return lambda e, i: fx.fixpoint(e, i, mode="jit", backend=be,
                                        max_iters=max_iters)

    def serve_chunk_fn(self, chunk_iters):
        be = self._backend()
        return lambda e, y, d, it: fx._resume_chunk(
            e, y, d, it, max_iters=chunk_iters, backend=be)


@register
class DenseVectorRunner(Runner):
    """Dense semiring matmul rounds — wins when E itself is dense."""

    name = "vector_dense"
    chunkable = True

    def operand(self, ctx):
        if not isinstance(ctx.edges, SparseRelation):
            return ctx.edges
        dense = ctx.extras.get("dense_edges")
        if dense is None:
            dense = ctx.extras["dense_edges"] = ctx.edges.to_dense()
        return dense

    def full_fn(self, ctx):
        sr, mi = sr_mod.get(ctx.semiring), ctx.max_iters
        return jax.jit(lambda e, i: _dense_vector_fixpoint(e, i, sr, mi))

    def batched_fn(self, plan, max_iters):
        sr = sr_mod.get(plan.strata[0].vf.semiring)
        return jax.jit(lambda e, i: _batched_dense_vector_fixpoint(
            e, i, sr, max_iters))

    def run_chunk(self, ctx, state, budget):
        edge = self.operand(ctx)
        key = ("chunk", self.name, budget)
        fn = ctx.extras.get(key)
        if fn is None:
            from repro.kernels import ops as kops
            sr = sr_mod.get(ctx.semiring)

            def adv(d):
                # carry is (n, B); the dense advance is the same ⊗/⊕
                # contraction as SpMM over the 0̄-filled matrix, so the
                # hand-off stays bit-exact (⊕ with 0̄ is identity)
                return kops.semiring_matmul(sr, d.T, edge).T

            fn = ctx.extras[key] = jax.jit(
                lambda y, d, it: fx._chunk_loop(None, y, d, it, sr,
                                                budget, advance=adv))
        y, d, it = fn(np.asarray(state.y), np.asarray(state.delta),
                      np.asarray(state.iters, np.int32))
        st = fx.FixpointState(y, d, it, state.semiring, state.batched)
        return st, st.stats()


@register
class ShardedRunner(_SparseRunner):
    """Graph-axis row-partitioned SpMM loop (DESIGN.md §6)."""

    name = "sparse_sharded"
    chunkable = True

    def feasible(self, ctx):
        return ctx.mesh is not None and super().feasible(ctx)

    def operand(self, ctx):
        es = ctx.extras.get("sharded_edges")
        if es is None:
            from repro.distributed.datalog import shard_relation
            es = ctx.extras["sharded_edges"] = shard_relation(ctx.edges,
                                                              ctx.mesh)
        return es

    def full_fn(self, ctx):
        from repro.distributed.datalog import sharded_seminaive_fixpoint
        m, mi = ctx.mesh, ctx.max_iters
        return jax.jit(lambda e, i: sharded_seminaive_fixpoint(
            e, i, mesh=m, max_iters=mi))

    def batched_fn(self, plan, max_iters):
        from repro.core import planner
        from repro.distributed.datalog import sharded_seminaive_fixpoint
        mesh = planner.exec_mesh(plan)
        return jax.jit(lambda e, i: sharded_seminaive_fixpoint(
            e, i, mesh=mesh, max_iters=max_iters))

    def run_chunk(self, ctx, state, budget):
        es = self.operand(ctx)
        key = ("chunk", self.name, budget)
        fn = ctx.extras.get(key)
        if fn is None:
            from repro.distributed.datalog import sharded_resume_chunk
            m = ctx.mesh
            fn = ctx.extras[key] = jax.jit(
                lambda y, d, it: sharded_resume_chunk(
                    es, y, d, it, mesh=m, max_iters=budget))
        y, d, it = fn(np.asarray(state.y), np.asarray(state.delta),
                      np.asarray(state.iters, np.int32))
        st = fx.FixpointState(y, d, it, state.semiring, state.batched)
        return st, st.stats()


def _batched_dense_vector_fixpoint(edge, init, sr, max_iters):
    """The vectorized ``x = init ⊕ x ⊗ E`` GSN step over a dense E for a
    ``(B, n)`` init pack — the one dense vector runner shared by
    :func:`repro.core.planner.execute_plan` (B = 1) and
    :func:`repro.core.planner.compile_batched`."""
    from repro.core import fixpoint
    from repro.kernels import ops as kops

    def ico(s):
        return {"x": sr.add(init, kops.semiring_matmul(sr, s["x"], edge))}

    def dico(s):
        return {"x": kops.semiring_matmul(sr, s["x"], edge)}

    x0 = {"x": sr.zeros(init.shape)}
    y, iters = fixpoint.batched_seminaive_fixpoint(
        ico, dico, x0, {"x": sr}, max_iters=max_iters)
    return y["x"], iters


def _dense_vector_fixpoint(edge, init, sr, max_iters):
    y, iters = _batched_dense_vector_fixpoint(edge, init.reshape(1, -1),
                                              sr, max_iters)
    return y[0], iters[0]


# --------------------------------------------------------------------------
# Dense engine runners (whole-stratum; not chunkable)
# --------------------------------------------------------------------------


class _IcoRunner(Runner):
    vector = False

    def _prep(self, stratum, cur_db, hints):
        from repro.core import program as prog_mod
        ico = prog_mod.make_ico(stratum, cur_db, hints)
        x0 = prog_mod.init_state(stratum, cur_db, hints)
        return ico, x0


@register
class DenseGsnRunner(_IcoRunner):
    name = "dense_gsn"

    def stratum_fn(self, stratum, cur_db, hints, max_iters):
        from repro.core import fixpoint
        from repro.core import program as prog_mod
        ico, x0 = self._prep(stratum, cur_db, hints)
        srs = {n: sr_mod.get(cur_db.schema[n].semiring)
               for n in stratum.idbs}
        dico = prog_mod.make_delta_ico(stratum, cur_db, hints)
        fn = jax.jit(lambda x0: fixpoint.seminaive_fixpoint(
            ico, dico, x0, srs, max_iters=max_iters))
        return fn, x0


@register
class DenseNaiveRunner(_IcoRunner):
    name = "dense_naive"

    def stratum_fn(self, stratum, cur_db, hints, max_iters):
        from repro.core import fixpoint
        ico, x0 = self._prep(stratum, cur_db, hints)
        fn = jax.jit(lambda x0: fixpoint.naive_fixpoint(
            ico, x0, max_iters=max_iters))
        return fn, x0


@register
class DenseHostRunner(_IcoRunner):
    name = "dense_host"

    def stratum_fn(self, stratum, cur_db, hints, max_iters):
        from repro.core import fixpoint
        ico, x0 = self._prep(stratum, cur_db, hints)

        def fn(x0, ico=ico):  # python loop, per-iteration visibility
            return fixpoint.host_fixpoint(ico, x0, max_iters=max_iters)

        return fn, x0


# --------------------------------------------------------------------------
# The adaptive executor
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ReplanEvent:
    """One mid-fixpoint runner switch, as logged in ``explain(plan)``."""

    chunk: int           # 0-based index of the chunk just finished
    iteration: int       # global iteration at the switch boundary
    frontier_nnz: int
    density: float
    from_runner: str
    to_runner: str
    est_from: float      # incumbent's priced next round (ns)
    est_to: float        # challenger's priced next round (ns)


@dataclasses.dataclass
class AdaptiveRun:
    """Execution trace of one adaptive fixpoint: per-chunk frontier
    observations plus the switch history (rendered by ``explain``)."""

    start_runner: str
    final_runner: str
    chunks: list
    switches: list
    policy: adaptive.ReplanPolicy


def adaptive_fixpoint(ctx: RunnerContext, *, start: str,
                      candidates=(), policy=None, observer=None):
    """Execute the fixpoint in bounded chunks, re-pricing the remaining
    work at every chunk boundary and switching runners via warm hand-off
    when the :class:`~repro.sparse.adaptive.ReplanPolicy` allows.

    Returns ``(x*, iters, AdaptiveRun)``; the answer and per-row
    iteration counts are bit-exact with any static chunkable runner
    (shared GSN round body + exact carry hand-off).  ``observer``, if
    given, receives each chunk's :class:`~repro.sparse.fixpoint.
    FrontierStats` as it lands (the serve-metrics hook).
    """
    policy = policy if policy is not None else adaptive.ReplanPolicy()
    cands = [start] + [c for c in candidates if c != start]
    cands = [c for c in cands
             if c in RUNNER_REGISTRY and get(c).chunkable
             and get(c).feasible(ctx)]
    if start not in cands:
        raise ValueError(f"start runner {start!r} is not a feasible "
                         f"chunkable runner here")
    state = fx.FixpointState.cold(ctx.edges, ctx.init)
    current = start
    trace = AdaptiveRun(start, start, [], [], policy)
    rounds_done = 0
    while not state.converged and rounds_done < ctx.max_iters:
        budget = int(min(policy.chunk_iters, ctx.max_iters - rounds_done))
        state, stats = get(current).run_chunk(ctx, state, budget)
        # a chunk only stops early on global convergence, so a
        # non-converged chunk ran exactly `budget` global rounds
        rounds_done += budget
        trace.chunks.append(stats)
        if observer is not None:
            observer(stats)
        if state.converged or rounds_done >= ctx.max_iters:
            break
        if len(cands) < 2:
            continue  # nothing to re-plan against; keep chunking
        ests = {c: get(c).estimate(ctx, state) for c in cands}
        best = min(ests, key=lambda c: (ests[c].total, c != current, c))
        chunk_index = len(trace.chunks) - 1
        since = chunk_index - trace.switches[-1].chunk \
            if trace.switches else chunk_index + 1
        if best != current and policy.should_switch(
                ests[current].total, ests[best].total,
                chunk_index=chunk_index, chunks_since_switch=since,
                switches=len(trace.switches)):
            trace.switches.append(ReplanEvent(
                chunk=chunk_index, iteration=stats.iteration,
                frontier_nnz=stats.nnz, density=stats.density,
                from_runner=current, to_runner=best,
                est_from=ests[current].total, est_to=ests[best].total))
            current = best
    trace.final_runner = current
    y, iters = get(current).finalize(ctx, state)
    return y, iters, trace
