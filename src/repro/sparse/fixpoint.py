"""Frontier-based semi-naive fixpoint over sparse S-relations.

Solves the linear vector equation (the paper's GH-form after the FGH
rewrite of BM/CC/SSSP/MLM-style programs, Sec. 3.1):

    x[y]  =  init[y] ⊕ ⊕_z x[z] ⊗ E[z, y]

with ``E`` a binary :class:`~repro.sparse.coo.SparseRelation`.  Two
execution modes share GSN semantics with
:func:`repro.core.fixpoint.seminaive_fixpoint` (identical per-iteration
states, so the runners are interchangeable mid-stream):

* ``mode="jit"`` — a single ``jax.lax.while_loop``; Δ is a length-n
  vector whose re-derivation costs O(nnz(E)) per iteration via
  :func:`repro.sparse.contract.vspm` (vs. the dense engine's O(n²)).
  Staged, pjit-shardable, TPU-ready.
* ``mode="frontier"`` — host worklist evaluation (Fan et al.; FlowLog):
  Δ is a **sparse worklist of changed tuples**.  Each round expands only
  the CSR adjacency rows of frontier vertices, so total work over the
  whole fixpoint is O(Σ_rounds Σ_{z ∈ frontier} deg(z)) ≤ O(nnz · depth),
  and per-round work is proportional to the frontier, not the graph.

``mode="auto"`` picks "frontier" on CPU hosts and "jit" on accelerators;
program-level routing between these and the dense runners is the
cost-based planner's job (:mod:`repro.core.planner`, DESIGN.md §4).

**Batched multi-source serving (DESIGN.md §3):** ``init`` may be a
``(B, n)`` frontier matrix — one row per source.  ``mode="jit"`` then
advances all B sources in a single ``lax.while_loop`` whose per-iteration
step is one SpMM (`repro.sparse.contract.spmm`) instead of B SpMVs, with
a per-row convergence mask so each source's iteration count matches its
single-source run exactly; the carry is kept in the (n, B) layout so
gathers/scatters move contiguous B-wide rows and the batch axis can be
sharded across devices (``query_batch`` logical axis).  ``iters`` comes
back as a ``(B,)`` per-source vector.  Rows whose init is all-0̄ are
inert — the serve loop uses them as batch padding.

**𝔹 chunks pull packed words:** the bounded chunk (:func:`_chunk_loop`,
the serve pools' :class:`CompiledChunk`) scatters no B-wide rows for 𝔹.
With no ``advance=`` override and no active mesh it packs the lanes 32
to a uint32 word and runs each round as a pull over the operator's
dst-sorted :class:`PullView` (gather, segmented OR, read of run ends),
built once per operator on the device.  Other semirings keep the
gather/scatter SpMM.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import semiring as sr_mod
from repro.sparse import contract
from repro.sparse.coo import SparseRelation


@dataclasses.dataclass
class FrontierStats:
    """Frontier observations from one fixpoint run or one bounded chunk.

    Frontier mode fills the per-round lists (worklist sizes and expanded
    edge counts).  Chunked execution (:func:`fixpoint` with ``budget=``,
    the adaptive executor, the serve steppers) instead reports the
    *carry* observed at the chunk boundary: ``nnz`` live Δ entries,
    their ``density`` over the ``(B, n)`` carry, at global iteration
    ``iteration`` — the re-planning signal of DESIGN.md §10.
    """

    frontier_sizes: list[int]
    edges_expanded: list[int]
    nnz: int = 0
    density: float = 0.0
    iteration: int = 0

    @property
    def total_edges(self) -> int:
        return int(sum(self.edges_expanded))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FixpointState:
    """The resumable carry of a GSN fixpoint — what every runner consumes
    and produces (DESIGN.md §10).

    Invariant (the warm-restart contract of :func:`resume_fixpoint`):
    ``y`` is a pre-fixpoint (``y ≤ F(y)``) and ``delta = F(y) ⊖ y`` its
    pending frontier, so any runner sharing the GSN round body can pick
    the pair up mid-stream and converge to the identical answer.  The
    arrays live in the canonical batched ``(B, n)`` layout (``B = 1``
    for a single source — ``batched`` remembers whether the caller's
    init had a batch axis); ``iters`` is the per-row ``(B,)`` iteration
    counter carried across chunks.  Registered as a jax pytree so
    compiled chunk bodies can take it apart for free; the observation
    helpers (``frontier_nnz``/``density``/``converged``) pull the Δ to
    host, so call them at chunk boundaries, not inside traced code.
    """

    y: object
    delta: object
    iters: object
    semiring: str = "bool"
    batched: bool = True

    def tree_flatten(self):
        return (self.y, self.delta, self.iters), (self.semiring,
                                                  self.batched)

    @classmethod
    def tree_unflatten(cls, aux, children):
        y, delta, iters = children
        return cls(y, delta, iters, *aux)

    @classmethod
    def cold(cls, edges: SparseRelation, init) -> "FixpointState":
        """Seed a cold start: ``y = 0̄``, ``delta = init ⊖ 0̄`` — exactly
        the first carry of the staged runners (``0̄ ⊗ E = 0̄``, so the
        cold Δ is just the init's live entries)."""
        srn = sr_mod.get(edges.semiring, lib="np")
        i2 = np.asarray(init, srn.dtype)
        batched = i2.ndim == 2
        if not batched:
            i2 = i2[None]
        y0 = np.full(i2.shape, srn.zero, srn.dtype)
        d0 = srn.minus(i2, y0)
        return cls(y0, d0, np.zeros(i2.shape[0], np.int32),
                   edges.semiring, batched)

    @property
    def batch(self) -> int:
        return int(np.shape(self.y)[0])

    @property
    def n(self) -> int:
        return int(np.shape(self.y)[1])

    def frontier_nnz(self) -> int:
        """Live (non-0̄) Δ entries across all rows (host reduction)."""
        zero = sr_mod.get(self.semiring, lib="np").zero
        return int((np.asarray(self.delta) != zero).sum())

    def density(self) -> float:
        return self.frontier_nnz() / max(1, self.batch * self.n)

    def live_rows(self) -> int:
        zero = sr_mod.get(self.semiring, lib="np").zero
        return int((np.asarray(self.delta) != zero).any(axis=1).sum())

    @property
    def converged(self) -> bool:
        return self.frontier_nnz() == 0

    def stats(self) -> FrontierStats:
        """Chunk-boundary observation: the re-planning signal."""
        nnz = self.frontier_nnz()
        return FrontierStats([], [], nnz=nnz,
                             density=nnz / max(1, self.batch * self.n),
                             iteration=int(np.max(np.asarray(self.iters),
                                                  initial=0)))

    def solution(self):
        """``(x*, iters)`` in the caller's original shape — drops the
        synthetic batch axis when the seeding init was 1-D."""
        if self.batched:
            return self.y, np.asarray(self.iters, np.int32)
        return (jnp.asarray(self.y)[0] if not isinstance(self.y, np.ndarray)
                else self.y[0]), int(np.asarray(self.iters)[0])


def fixpoint(edges: SparseRelation, init=None, *, state=None,
             budget=None, max_iters: int = 10_000, mode: str = "auto",
             backend: str = "jnp"):
    """Least fixpoint of ``x = init ⊕ vspm(x, edges)`` — the one sparse
    entrypoint (cold, warm, and chunked; DESIGN.md §10).

    Pass exactly one of ``init`` (cold start) or ``state`` (a
    :class:`FixpointState` carry to resume).  With ``budget=None`` the
    run converges and returns ``(x*, iters)`` — a 2-D ``(B, n)`` init
    runs the batched multi-source path (module docstring) with a
    ``(B,)`` iters vector, and a resumed run's iters *include* the
    rounds already in the carry.  With ``budget=k`` the loop advances
    **at most k rounds** and returns the updated :class:`FixpointState`
    instead — chain calls to interleave work, observe the frontier, or
    hand the carry to a different runner (the adaptive executor's unit,
    :mod:`repro.core.runners`).

    ``mode`` is ``"auto"`` (frontier worklist on CPU hosts, staged jit
    on accelerators; budgeted calls default to the staged chunk body),
    ``"jit"`` or ``"frontier"``.  ``backend`` selects the SpMM execution
    of the staged loop (DESIGN.md §9): ``"jnp"`` is the traceable
    gather/scatter composition, ``"pallas"`` the fused TPU kernel
    (per-operator compiled closures), ``"fused"`` the host-numpy fused
    loop (bit-packed 𝔹 lanes on CPU).  The non-jnp backends need a
    concrete ``edges``.
    """
    if (init is None) == (state is None):
        raise ValueError("fixpoint() takes exactly one of init= or state=")
    if budget is None:
        if state is None:
            y, iters, _ = _dispatch(edges, init, max_iters=max_iters,
                                    mode=mode, backend=backend)
            return y, iters
        y, iters, _ = _dispatch(edges, None, max_iters=max_iters,
                                mode=mode, backend=backend,
                                warm=(state.y, state.delta))
        iters = np.asarray(state.iters, np.int32) \
            + np.asarray(iters, np.int32)
        if not state.batched:
            return jnp.asarray(y)[0], int(iters[0])
        return y, iters
    st = state if state is not None else FixpointState.cold(edges, init)
    budget = int(min(budget, max_iters))
    if mode == "frontier":
        y, d, it = _frontier_chunk(edges, st.y, st.delta, st.iters, budget)
    else:
        # the staged chunk body is the carry-exact unit shared with the
        # serve loop; "auto" means it here — a budgeted frontier pass
        # must be asked for explicitly
        y, d, it = _resume_chunk(edges, st.y, st.delta, st.iters,
                                 max_iters=budget, backend=backend)
    return FixpointState(y, d, it, st.semiring, st.batched)


def sparse_seminaive_fixpoint(edges: SparseRelation, init, *,
                              max_iters: int = 10_000,
                              mode: str = "auto",
                              backend: str = "jnp"):
    """Deprecated alias of :func:`fixpoint` (cold start)."""
    warnings.warn("sparse_seminaive_fixpoint is deprecated; use "
                  "fixpoint(edges, init, ...)", DeprecationWarning,
                  stacklevel=2)
    y, iters, _ = _dispatch(edges, init, max_iters=max_iters, mode=mode,
                            backend=backend)
    return y, iters


def sparse_seminaive_fixpoint_stats(edges: SparseRelation, init, *,
                                    max_iters: int = 10_000,
                                    mode: str = "frontier"):
    """Instrumented variant: returns ``(x*, iters, FrontierStats|None)``.

    Batched frontier runs return a list of per-source FrontierStats.
    """
    return _dispatch(edges, init, max_iters=max_iters, mode=mode)


def resume_fixpoint(edges: SparseRelation, y0, d0, *,
                    max_iters: int = 10_000, mode: str = "auto"):
    """Re-converge ``x = init ⊕ x ⊗ E`` from a warm ``(y0, d0)`` pair.

    The GSN loop body is *identical* to :func:`sparse_seminaive_fixpoint`
    — only the carry's starting point differs: ``y0`` is a known
    pre-fixpoint (``y0 ≤ F(y0)``) and ``d0 = F(y0) ⊖ y0`` its pending
    delta.  Delta-restart maintenance (:mod:`repro.incremental`,
    DESIGN.md §5) seeds ``d0`` from only the touched edges, so the
    re-convergence explores just the affected region instead of the whole
    key space.  ``y0`` may be ``(B, n)`` for a batched repair (one SpMM
    per round, per-row convergence).

    Returns ``(x*, iters)``; ``iters`` counts only the *resumed* rounds.

    Deprecated: build a :class:`FixpointState` and call
    ``fixpoint(edges, state=state)`` (whose iters *include* the carry's).
    """
    warnings.warn("resume_fixpoint is deprecated; use fixpoint(edges, "
                  "state=FixpointState(y0, d0, ...))", DeprecationWarning,
                  stacklevel=2)
    return _dispatch(edges, None, max_iters=max_iters, mode=mode,
                     warm=(y0, d0))[:2]


def resume_fixpoint_chunk(edges: SparseRelation, y0, d0, it0, *,
                          max_iters: int, backend: str = "jnp"):
    """One bounded slice of the batched GSN loop, carry in and carry out.

    Advances the ``(B, n)`` pair ``(y0, d0)`` by **at most** ``max_iters``
    rounds of the exact :func:`_batched_jit_fixpoint` body (one SpMM per
    round, per-row convergence masks) and returns the full carry
    ``(y, d, it_rows)`` instead of just the solution — so a caller can
    chain chunks: splice new init columns into freed rows between calls,
    extract converged rows early, and never pay for a full re-convergence.
    This is the continuous-batching serve loop's compiled unit
    (:mod:`repro.serve.slots`, DESIGN.md §7); jit it with ``max_iters``
    closed over so the chunk length is static.

    ``it0`` is the ``(B,)`` per-row iteration counter carried across
    chunks; rows whose Δ-row is all-0̄ are converged (or inert padding)
    and their counters stop.  Identical chaining invariant to
    :func:`resume_fixpoint`: ``y0`` is a pre-fixpoint and
    ``d0 = F(y0) ⊖ y0`` its pending delta, which the chunk preserves.

    ``backend`` as in :func:`fixpoint`; the non-jnp chunks memoize their
    compiled/host closures on the operator's cached SpMM plan, so
    callers need not (and must not) wrap them in ``jit``.

    Deprecated: use ``fixpoint(edges, state=state, budget=k)``.
    """
    warnings.warn("resume_fixpoint_chunk is deprecated; use "
                  "fixpoint(edges, state=state, budget=max_iters)",
                  DeprecationWarning, stacklevel=2)
    return _resume_chunk(edges, y0, d0, it0, max_iters=max_iters,
                         backend=backend)


def _resume_chunk(edges: SparseRelation, y0, d0, it0, *,
                  max_iters: int, backend: str = "jnp", view=None):
    """The chunk body behind :func:`fixpoint`'s ``budget=`` path and the
    (deprecated) :func:`resume_fixpoint_chunk` shim.  ``view`` is the
    operator's :class:`PullView` where the caller looked it up."""
    if edges.arity != 2 or edges.shape[0] != edges.shape[1]:
        raise ValueError(f"recursive expansion needs a square binary edge "
                         f"relation, got shape {edges.shape}")
    sr = sr_mod.get(edges.semiring)
    if sr.minus is None:
        raise ValueError(f"semiring {sr.name} lacks ⊖; "
                         "GSN needs an idempotent complete lattice")
    if backend != "jnp":
        return _fused_resume_chunk(edges, y0, d0, it0, max_iters, backend)
    return _chunk_loop(edges.as_jnp(), y0, d0, it0, sr, max_iters,
                       view=view)


def _dispatch(edges, init, *, max_iters, mode, warm=None, backend="jnp"):
    if edges.arity != 2 or edges.shape[0] != edges.shape[1]:
        raise ValueError(f"recursive expansion needs a square binary edge "
                         f"relation, got shape {edges.shape}")
    sr = sr_mod.get(edges.semiring)
    if sr.minus is None:
        raise ValueError(f"semiring {sr.name} lacks ⊖; "
                         "GSN needs an idempotent complete lattice")
    if backend == "fused":
        return _fused_host_fixpoint(edges, init, max_iters, warm=warm)
    if backend == "pallas":
        return _pallas_fixpoint(edges, init, sr, max_iters, warm=warm)
    if backend != "jnp":
        raise ValueError(f"unknown fixpoint backend {backend!r}")
    if mode == "auto":
        mode = "frontier" if jax.default_backend() == "cpu" else "jit"
    batched = np.ndim(init if warm is None else warm[0]) == 2
    if mode == "jit":
        jw = None if warm is None else (jnp.asarray(warm[0]),
                                        jnp.asarray(warm[1]))
        if batched:
            y, iters = _batched_jit_fixpoint(
                edges.as_jnp(),
                None if init is None else jnp.asarray(init), sr,
                max_iters, warm=jw)
        else:
            y, iters = _jit_fixpoint(
                edges.as_jnp(),
                None if init is None else jnp.asarray(init), sr,
                max_iters, warm=jw)
        return y, iters, None
    if mode == "frontier":
        if batched:
            return _batched_frontier_fixpoint(edges, init, max_iters,
                                              warm=warm)
        y, _, iters, stats = _frontier_fixpoint(edges, init, max_iters,
                                                warm=warm)
        return y, iters, stats
    raise ValueError(f"unknown mode {mode!r}")


# --------------------------------------------------------------------------
# Staged path: lax.while_loop, Δ re-derived in O(nnz) by vspm
# --------------------------------------------------------------------------


def _jit_fixpoint(edges: SparseRelation, init, sr, max_iters: int, *,
                  warm=None, advance=None):
    adv = advance or (lambda d: contract.vspm(d, edges))
    if warm is None:
        x0 = jnp.full_like(init, sr.zero)
        d0 = sr.minus(sr.add(init, adv(x0)), x0)
    else:
        x0, d0 = warm

    live0 = jnp.asarray(True) if warm is None else jnp.any(d0 != sr.zero)

    def cond(carry):
        y, d, changed, it = carry
        return jnp.logical_and(changed, it < max_iters)

    def body(carry):
        y, d, _, it = carry
        y_new = sr.add(y, d)
        d_new = sr.minus(adv(d), y_new)
        return y_new, d_new, jnp.any(d_new != sr.zero), it + 1

    y, _, _, iters = jax.lax.while_loop(
        cond, body, (x0, d0, live0, jnp.asarray(0)))
    return y, iters


def _batched_jit_fixpoint(edges: SparseRelation, init, sr, max_iters: int,
                          *, warm=None, advance=None):
    """All B sources in one ``lax.while_loop``: SpMM frontier advance,
    per-row convergence masks, per-row iteration counts.

    The carry lives in the (n, B) layout so every gather/scatter moves a
    contiguous B-wide row per edge (contract.spmm); the batch axis is
    annotated with the ``query_batch`` logical axis so an active mesh
    shards it across devices (no-op otherwise).  ``warm`` is an optional
    ``(y0, d0)`` pair of (B, n) arrays for delta-restart repair.
    ``advance`` overrides the (n, B) → (n, B) frontier-advance SpMM —
    the fused-kernel backends inject their closure here.
    """
    from repro.distributed import sharding as sh

    adv = advance or (lambda d: contract.spmm(edges, d, transpose=True))
    if warm is None:
        b = init.shape[0]
        x0 = jnp.full(init.shape[::-1], sr.zero, sr.dtype)    # (n, B)
        i_nb = sh.constrain(jnp.asarray(init).T,
                            ("vertex", "query_batch"))
        d0 = sr.minus(sr.add(i_nb, adv(x0)), x0)
    else:
        b = warm[0].shape[0]
        x0 = sh.constrain(warm[0].T, ("vertex", "query_batch"))
        d0 = sh.constrain(warm[1].T, ("vertex", "query_batch"))
    live0 = (jnp.ones((b,), bool) if warm is None
             else jnp.any(d0 != sr.zero, axis=0))

    def cond(carry):
        y, d, live, it_rows, it = carry
        return jnp.logical_and(jnp.any(live), it < max_iters)

    def body(carry):
        y, d, live, it_rows, it = carry
        y_new = sh.constrain(sr.add(y, d), ("vertex", "query_batch"))
        d_new = sr.minus(adv(d), y_new)
        d_new = sh.constrain(d_new, ("vertex", "query_batch"))
        # a source's row of Δ going all-0̄ is its convergence: from then on
        # the row re-derives 0̄ forever (δF(0̄) ⊖ Y = 0̄), so masking is
        # only needed for the per-row iteration counts, not the values.
        live_new = jnp.any(d_new != sr.zero, axis=0)
        return y_new, d_new, live_new, it_rows + live, it + 1

    y, _, _, it_rows, _ = jax.lax.while_loop(
        cond, body, (x0, d0, live0, jnp.zeros((b,), jnp.int32),
                     jnp.asarray(0)))
    return y.T, it_rows


# --------------------------------------------------------------------------
# Fused-kernel backends: same GSN loop, SpMM via kernels/coo_spmm
# --------------------------------------------------------------------------


def _pallas_fixpoint(edges, init, sr, max_iters, *, warm=None):
    """The jit GSN loop with the fused Pallas SpMM as frontier advance.

    The operator's edge-tile geometry is host-planned, so the whole
    while-loop is compiled *per operator*: a jitted closure over the
    concrete edges, memoized on the cached :class:`SpmmPlan` — repeat
    calls (the serving loop) re-enter compiled code directly.
    """
    from repro.kernels import coo_spmm, ops as kops

    interp = kops.pallas_interpret()
    plan = coo_spmm.plan_geometry(edges, transpose=True)
    batched = np.ndim(init if warm is None else warm[0]) == 2
    key = ("fixpoint", batched, warm is None, max_iters, interp)
    fn = plan.jit_cache.get(key)
    if fn is None:
        ej = edges.as_jnp()

        def adv(d):
            return coo_spmm.spmm_pallas(plan, d, interpret=interp)

        inner = _batched_jit_fixpoint if batched else _jit_fixpoint
        if warm is None:
            fn = jax.jit(lambda i: inner(ej, i, sr, max_iters, advance=adv))
        else:
            fn = jax.jit(lambda y0, d0: inner(ej, None, sr, max_iters,
                                              warm=(y0, d0), advance=adv))
        plan.jit_cache[key] = fn
    if warm is None:
        y, iters = fn(jnp.asarray(init))
    else:
        y, iters = fn(jnp.asarray(warm[0]), jnp.asarray(warm[1]))
    return y, iters, None


def _fused_host_fixpoint(edges, init, max_iters, *, warm=None):
    """Host-numpy fused GSN loop — the CPU serving backend (DESIGN.md §9).

    For 𝔹 the whole carry lives bit-packed: ``y``/``Δ`` are (n, W)
    uint64 words and one round is a single ``bitwise_or.reduceat`` sweep
    (:func:`coo_spmm.bool_round_packed`) plus word-wise ``y |= Δ``,
    ``Δ &= ~y`` — ~64× fewer bytes per iteration than the (n, B) boolean
    gather/scatter.  Other lattices run :func:`coo_spmm.spmm_host`.
    Round structure, convergence masks, and per-row iteration counts
    mirror :func:`_batched_jit_fixpoint` exactly.
    """
    from repro.kernels import coo_spmm

    srn = sr_mod.get(edges.semiring, lib="np")
    plan = coo_spmm.plan_geometry(edges, transpose=True)
    batched = np.ndim(init if warm is None else warm[0]) == 2
    if warm is None:
        i2 = np.asarray(init)
        i2 = i2 if batched else i2[None]
        b = i2.shape[0]
        y0 = np.full((plan.n_in, b), srn.zero, srn.dtype)      # (n, B)
        d0 = srn.minus(srn.add(i2.T.astype(srn.dtype),
                               coo_spmm.spmm_host(plan, y0)), y0)
        live = np.ones(b, bool)
    else:
        y0w, d0w = np.asarray(warm[0]), np.asarray(warm[1])
        if not batched:
            y0w, d0w = y0w[None], d0w[None]
        b = y0w.shape[0]
        y0 = np.ascontiguousarray(y0w.T.astype(srn.dtype))
        d0 = np.ascontiguousarray(d0w.T.astype(srn.dtype))
        live = (d0 != srn.zero).any(axis=0)
    it_rows = np.zeros(b, np.int32)
    it = 0
    if edges.semiring == "bool":
        yw = coo_spmm.pack_lanes(y0.T)
        dw = coo_spmm.pack_lanes(d0.T)
        while live.any() and it < max_iters:
            it_rows += live
            np.bitwise_or(yw, dw, out=yw)
            dw = coo_spmm.bool_round_packed(plan, dw) & ~yw
            live = _packed_live(dw, b)
            it += 1
        y = coo_spmm.unpack_lanes(yw, b)                       # (B, n)
    else:
        y, d = y0, d0
        while live.any() and it < max_iters:
            it_rows += live
            y = srn.add(y, d)
            d = srn.minus(coo_spmm.spmm_host(plan, d), y)
            live = (d != srn.zero).any(axis=0)
            it += 1
        y = y.T
    if batched:
        return jnp.asarray(y), jnp.asarray(it_rows), None
    return jnp.asarray(y[0]), int(it_rows[0]), None


def _packed_live(words: np.ndarray, b: int) -> np.ndarray:
    """Per-lane liveness of a packed (n, W) Δ: lane has any bit set."""
    agg = np.bitwise_or.reduce(words, axis=0)                  # (W,)
    return np.unpackbits(agg.view(np.uint8),
                         bitorder="little")[:b].astype(bool)


def _fused_resume_chunk(edges, y0, d0, it0, max_iters, backend):
    """The non-jnp body of :func:`resume_fixpoint_chunk`.

    ``"pallas"`` memoizes a per-operator jitted chunk on the cached SpMM
    plan; ``"fused"`` runs the bounded host loop (packed 𝔹 rounds).
    """
    from repro.kernels import coo_spmm, ops as kops

    sr = sr_mod.get(edges.semiring)
    plan = coo_spmm.plan_geometry(edges, transpose=True)
    if backend == "pallas":
        interp = kops.pallas_interpret()
        key = ("chunk", max_iters, interp)
        fn = plan.jit_cache.get(key)
        if fn is None:
            ej = edges.as_jnp()

            def fixpoint_chunk(y, d, it):
                return _chunk_loop(ej, y, d, it, sr, max_iters,
                                   advance=lambda dd: coo_spmm.spmm_pallas(
                                       plan, dd, interpret=interp))
            fn = plan.jit_cache[key] = jax.jit(fixpoint_chunk)
        return fn(jnp.asarray(y0), jnp.asarray(d0), jnp.asarray(it0))
    if backend != "fused":
        raise ValueError(f"unknown fixpoint backend {backend!r}")
    srn = sr_mod.get(edges.semiring, lib="np")
    b = np.asarray(y0).shape[0]
    it_rows = np.asarray(it0, np.int32).copy()
    it = 0
    if edges.semiring == "bool":
        yw = coo_spmm.pack_lanes(np.asarray(y0))
        dw = coo_spmm.pack_lanes(np.asarray(d0))
        while it < max_iters and dw.any():
            it_rows += _packed_live(dw, b)
            np.bitwise_or(yw, dw, out=yw)
            dw = coo_spmm.bool_round_packed(plan, dw) & ~yw
            it += 1
        y, d = coo_spmm.unpack_lanes(yw, b), coo_spmm.unpack_lanes(dw, b)
    else:
        y = np.ascontiguousarray(np.asarray(y0).T.astype(srn.dtype))
        d = np.ascontiguousarray(np.asarray(d0).T.astype(srn.dtype))
        while it < max_iters and (d != srn.zero).any():
            it_rows += (d != srn.zero).any(axis=0)
            y = srn.add(y, d)
            d = srn.minus(coo_spmm.spmm_host(plan, d), y)
            it += 1
        y, d = y.T, d.T
    return jnp.asarray(y), jnp.asarray(d), jnp.asarray(it_rows)


def _chunk_loop(edges, y0, d0, it0, sr, max_iters, *, advance=None,
                view=None):
    """The traceable chunk body shared by the jnp, dense and pallas
    chunks.  A 𝔹 chunk with no ``advance`` override and no active mesh
    runs the packed pull round (:func:`_packed_chunk_loop`) over
    ``view``, which is built here, inside the trace, when not given."""
    from repro.distributed import sharding as sh

    if takes_pull_round(sr.name, advance):
        return _packed_chunk_loop(view if view is not None
                                  else pull_view(edges),
                                  y0, d0, it0, max_iters)
    adv = advance or (lambda d: contract.spmm(edges, d, transpose=True))
    y = sh.constrain(jnp.asarray(y0).T, ("vertex", "query_batch"))
    d = sh.constrain(jnp.asarray(d0).T, ("vertex", "query_batch"))
    it_rows = jnp.asarray(it0, jnp.int32)

    def cond(carry):
        y, d, it_rows, it = carry
        return jnp.logical_and(jnp.any(d != sr.zero), it < max_iters)

    def body(carry):
        y, d, it_rows, it = carry
        live = jnp.any(d != sr.zero, axis=0)
        y_new = sh.constrain(sr.add(y, d), ("vertex", "query_batch"))
        with jax.named_scope("advance"):
            e_d = adv(d)
        d_new = sr.minus(e_d, y_new)
        d_new = sh.constrain(d_new, ("vertex", "query_batch"))
        return y_new, d_new, it_rows + live, it + 1

    y, d, it_rows, _ = jax.lax.while_loop(
        cond, body, (y, d, it_rows, jnp.asarray(0)))
    return y.T, d.T, it_rows


# --------------------------------------------------------------------------
# 𝔹 pull round: bit-packed lanes over a dst-sorted view of the edges
# --------------------------------------------------------------------------
#
# For 𝔹, ⊕ is OR, so 32 query lanes pack into one uint32 word per vertex
# and a round pulls words instead of pushing B-wide rows: gather each
# edge's source word in destination order, OR within each destination's
# run, read the run's last position.  No scatter, and no per-round sort
# of the destination indices, which XLA's scatter-max makes every round.


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PullView:
    """The operator's edges sorted by destination, for the packed round.

    ``src`` is each edge's source in that order; an edge whose value is
    0̄ (or a padding slot) has source ``n``, which reads no word.  ``off``
    is each edge's position within its destination's run, and ``last``
    each vertex's last position in the order (``cap`` for a vertex with
    no in-edges, which reads 0).  ``steps`` (static) doubling passes
    cover the longest run.
    """

    src: object     # (cap,) int32
    off: object     # (cap,) int32
    last: object    # (n,) int32
    steps: int

    def tree_flatten(self):
        return (self.src, self.off, self.last), (self.steps,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def takes_pull_round(semiring: str, advance=None) -> bool:
    """Whether a chunk runs the packed pull round: 𝔹, no ``advance``
    override, and no active mesh (whose ``query_batch`` axis packing
    would merge)."""
    from repro.distributed import sharding as sh
    return semiring == "bool" and advance is None \
        and sh.current_mesh() is None


_PULL_CACHE: dict[tuple[int, int], tuple[object, object, PullView]] = {}


def pull_view(edges: SparseRelation) -> PullView:
    """The operator's :class:`PullView`: built on the device with one
    sort, and cached per (coords, values) buffer pair, weakly, like the
    CSR index.  Traced edges build it inside the trace, uncached."""
    if isinstance(edges.coords, jax.core.Tracer) or \
            isinstance(edges.values, jax.core.Tracer):
        return _build_pull_view(edges, concrete=False)
    key = (id(edges.coords), id(edges.values))
    ent = _PULL_CACHE.get(key)
    if ent is not None and ent[0]() is edges.coords \
            and ent[1]() is edges.values:
        return ent[2]
    view = _build_pull_view(edges, concrete=True)

    def _evict(ref, k=key):
        cur = _PULL_CACHE.get(k)
        if cur is not None and ref in (cur[0], cur[1]):
            _PULL_CACHE.pop(k, None)

    try:
        _PULL_CACHE[key] = (weakref.ref(edges.coords, _evict),
                            weakref.ref(edges.values, _evict), view)
    except TypeError:  # pragma: no cover — all our buffers are weakrefable
        pass
    return view


def _build_pull_view(edges: SparseRelation, *, concrete: bool) -> PullView:
    src, off, last, longest = _pull_arrays(edges.coords, edges.values,
                                           n=edges.shape[0])
    # a concrete view takes as many doubling passes as its longest run
    # needs; a traced one as many as the capacity
    longest = int(longest) if concrete else src.shape[0]
    return PullView(src, off, last, max(0, longest - 1).bit_length())


@functools.partial(jax.jit, static_argnames="n")
def _pull_arrays(coords, values, *, n: int):
    coords = coords.astype(jnp.int32)
    cap = coords.shape[0]
    src = jnp.where(values.astype(bool), coords[:, 0], n)
    # padding slots carry dst = n, so they sort past every vertex
    dst, src = jax.lax.sort((coords[:, 1], src), num_keys=1)
    pos = jnp.arange(cap, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), dst[1:] != dst[:-1]])
    off = pos - jax.lax.cummax(jnp.where(first, pos, 0))
    vertex = jnp.arange(n, dtype=jnp.int32)
    last = jnp.searchsorted(dst, vertex, side="right").astype(jnp.int32) - 1
    has_in = (last >= 0) & (dst[jnp.maximum(last, 0)] == vertex)
    longest = jnp.max(jnp.where(dst < n, off, -1)) + 1
    return src, off, jnp.where(has_in, last, cap), longest


def _pack(x, words: int):
    """(B, n) bool → (W, n) uint32: lane ``32w + j`` is bit ``j`` of word
    ``w``; lanes past B stay 0."""
    b, n = x.shape
    x = jnp.pad(jnp.asarray(x, bool), ((0, 32 * words - b), (0, 0)))
    bits = x.reshape(words, 32, n).astype(jnp.uint32) \
        << jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    return jnp.sum(bits, axis=1, dtype=jnp.uint32)


def _unpack(words, b: int):
    """(W, n) uint32 → (B, n) bool."""
    bits = words[:, None, :] >> jnp.arange(32, dtype=jnp.uint32)[None, :,
                                                                   None]
    return (bits & 1).astype(bool).reshape(-1, words.shape[1])[:b]


def _live_lanes(words, b: int):
    """(B,) bool: lane has a bit set at some vertex."""
    agg = jax.lax.reduce(words, jnp.uint32(0), jax.lax.bitwise_or, (1,))
    return _unpack(agg[:, None], b)[:, 0]


def _take_words(words, idx):
    """``words[:, idx]``, 0 past the end: one gather of W-word columns.
    On a v5e it costs per index, not per byte: at W = 2 and 2^25 indices
    it takes 205 ms, and W gathers of single words 290 ms each."""
    return jnp.take(words, idx, axis=1, mode="fill", fill_value=0)


def _pull_round(view: PullView, words):
    """``E·Δ`` for packed 𝔹 lanes: (W, n) → (W, n) uint32.  Its three
    parts carry scopes of their own, for the trace's split."""
    with jax.named_scope("gather"):
        g = _take_words(words, view.src)                      # (W, cap)
    # segmented OR by doubling: after pass k each position holds the OR
    # of the last 2^(k+1) positions of its run
    with jax.named_scope("segment_or"):
        for k in range(view.steps):
            sh = 1 << k
            prev = jnp.pad(g[:, :-sh], ((0, 0), (sh, 0)))
            g = g | jnp.where(view.off >= sh, prev, jnp.uint32(0))
    with jax.named_scope("ends"):
        return _take_words(g, view.last)


def _packed_chunk_loop(view: PullView, y0, d0, it0, max_iters):
    """The chunk body on packed words: ``y ∪ Δ`` is ``|``, ``E·Δ ⊖ y``
    is ``& ~``.  Same rounds, masks and counts as :func:`_chunk_loop`;
    packs at entry and unpacks at exit, so the carry is (B, n) bool
    either side."""
    b = jnp.shape(y0)[0]
    w = -(-b // 32)
    y, d = _pack(y0, w), _pack(d0, w)
    it_rows = jnp.asarray(it0, jnp.int32)

    def cond(carry):
        y, d, it_rows, it = carry
        return jnp.logical_and(jnp.any(d != 0), it < max_iters)

    def body(carry):
        y, d, it_rows, it = carry
        live = _live_lanes(d, b)
        y_new = y | d
        with jax.named_scope("advance"):
            e_d = _pull_round(view, d)
        return y_new, e_d & ~y_new, it_rows + live, it + 1

    y, d, it_rows, _ = jax.lax.while_loop(
        cond, body, (y, d, it_rows, jnp.asarray(0)))
    return _unpack(y, b), _unpack(d, b), it_rows


class CompiledChunk:
    """The staged loop's compiled chunk ``(e, y, d, it) → (y, d, it)``
    over a (B, n) carry, for the serve pools and ``sparse_jit``'s
    ``run_chunk``.  The operator's :class:`PullView` is looked up
    outside the jit and passed in as an argument; the jitted program is
    named ``fixpoint_chunk``, so a device trace calls it
    ``jit_fixpoint_chunk``."""

    def __init__(self, max_iters: int):
        def fixpoint_chunk(e, view, y, d, it):
            return _resume_chunk(e, y, d, it, max_iters=max_iters,
                                 view=view)
        self._jit = jax.jit(fixpoint_chunk)

    @staticmethod
    def packs(edges: SparseRelation) -> bool:
        """Whether this chunk's rounds take the packed pull round."""
        return takes_pull_round(edges.semiring)

    def __call__(self, e, y, d, it):
        view = pull_view(e) if self.packs(e) else None
        return self._jit(e, view, y, d, it)


# --------------------------------------------------------------------------
# Host path: true sparse worklist over a CSR view of the edges
# --------------------------------------------------------------------------
#
# The CSR adjacency is cached per coords buffer (weakref-evicted, like the
# planner's fingerprint tokens) and — the incremental-maintenance piece,
# DESIGN.md §5 — ``SparseRelation.apply_delta`` *extends* the parent's
# index with an O(nnz(Δ)) unsorted overlay instead of re-sorting, so under
# streaming updates the per-update index work is proportional to the
# delta.  Overlays are compacted into the sorted base once they exceed a
# quarter of it (the child is simply left unregistered, so its next
# frontier solve rebuilds — classic LSM-style amortization).


@dataclasses.dataclass
class _CsrIndex:
    """Sorted CSR base + unsorted appended overlay of one edge relation."""

    counts: np.ndarray   # (n,) out-degrees of the sorted base
    starts: np.ndarray   # (n,) row starts into src/dst/w
    src: np.ndarray
    dst: np.ndarray
    w: np.ndarray
    xsrc: np.ndarray     # overlay rows (appended by apply_delta)
    xdst: np.ndarray
    xw: np.ndarray


_CSR_CACHE: dict[tuple[int, int, bool],
                 tuple[object, object, _CsrIndex]] = {}
_EMPTY = np.zeros(0, np.int64)


def _csr_lookup(rel: SparseRelation, transpose: bool = False
                ) -> _CsrIndex | None:
    # keyed on BOTH buffers: transposes share values and semiring casts
    # share coords — either alone would alias distinct relations
    ent = _CSR_CACHE.get((id(rel.coords), id(rel.values), transpose))
    if ent is not None and ent[0]() is rel.coords \
            and ent[1]() is rel.values:
        return ent[2]
    return None


def _csr_store(rel: SparseRelation, idx: _CsrIndex,
               transpose: bool = False) -> None:
    key = (id(rel.coords), id(rel.values), transpose)

    def _evict(ref, k=key):
        cur = _CSR_CACHE.get(k)
        if cur is not None and ref in (cur[0], cur[1]):
            _CSR_CACHE.pop(k, None)

    try:
        _CSR_CACHE[key] = (weakref.ref(rel.coords, _evict),
                           weakref.ref(rel.values, _evict), idx)
    except TypeError:  # pragma: no cover — all our buffers are weakrefable
        pass


def csr_index(edges: SparseRelation, *,
              transpose: bool = False) -> _CsrIndex:
    """The (cached) host CSR adjacency of a binary sparse relation.

    ``transpose=True`` indexes **in**-edges: row ``a`` of the index lists
    the ``(z, E[z, a])`` pairs, which is what a maintenance recount
    ``d₀[a] = init[a] ⊕ ⊕_z y₀[z] ⊗ E[z, a]`` walks (DESIGN.md §11).
    Both orientations share the cache (separate key slots), so the
    transpose is built once per buffer identity, not per recount.
    """
    idx = _csr_lookup(edges, transpose)
    if idx is None:
        eh = edges.as_np()
        k = int(eh.nnz)
        a, b = (1, 0) if transpose else (0, 1)
        src = eh.coords[:k, a].astype(np.int64)
        dst = eh.coords[:k, b].astype(np.int64)
        w = eh.values[:k]
        order = np.argsort(src, kind="stable")
        src, dst, w = src[order], dst[order], w[order]
        counts = np.bincount(src, minlength=edges.shape[a])
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        idx = _CsrIndex(counts, starts, src, dst, w,
                        _EMPTY, _EMPTY, w[:0])
        _csr_store(edges, idx, transpose)
    return idx


def register_delta(parent: SparseRelation, child: SparseRelation,
                   coords: np.ndarray, values: np.ndarray) -> None:
    """``child = parent ⊕ appended rows``: give the child the parent's
    cached CSR plus an O(nnz(Δ)) overlay (no-op when the parent was
    never indexed, or when the grown overlay warrants a compaction).
    Both orientations propagate when cached."""
    for transpose in (False, True):
        pidx = _csr_lookup(parent, transpose)
        if pidx is None:
            continue
        a, b = (1, 0) if transpose else (0, 1)
        xsrc = np.concatenate([pidx.xsrc, coords[:, a].astype(np.int64)])
        if len(xsrc) > max(1024, len(pidx.src) // 4):
            continue  # compaction point: child rebuilds a sorted base
        xdst = np.concatenate([pidx.xdst, coords[:, b].astype(np.int64)])
        xw = np.concatenate([pidx.xw, values])
        _csr_store(child,
                   _CsrIndex(pidx.counts, pidx.starts, pidx.src,
                             pidx.dst, pidx.w, xsrc, xdst, xw),
                   transpose)


def register_delete(parent: SparseRelation, child: SparseRelation,
                    coords: np.ndarray) -> None:
    """``child = parent ∖ deleted keys``: hand the child a copy of any
    cached CSR whose deleted entries have their weights set to 0̄.

    A 0̄ weight annihilates under ⊗ (``x ⊗ 0̄ = 0̄`` in every semiring
    here) and 0̄ is the ⊕-identity, so a poisoned entry contributes
    nothing to frontier expansion or recount scatters — the row stays in
    place and ``counts``/``starts`` are untouched, which is what makes a
    one-edge delete O(deg) instead of an O(nnz log nnz) re-sort
    (DESIGN.md §11).  Cost: O(nnz(Δ) · deg) probe into the sorted base
    plus an O(overlay) key scan.
    """
    coords = np.asarray(coords, np.int64).reshape(-1, 2)
    sr = sr_mod.get(parent.semiring, lib="np")
    zero = np.asarray(sr.zero, sr.dtype)
    for transpose in (False, True):
        pidx = _csr_lookup(parent, transpose)
        if pidx is None:
            continue
        a, b = (1, 0) if transpose else (0, 1)
        dsrc = coords[:, a]
        ddst = coords[:, b]
        w = pidx.w.copy()
        n_rows = len(pidx.counts)
        for s, t in zip(dsrc, ddst):
            if not (0 <= s < n_rows):
                continue
            lo = pidx.starts[s]
            hi = lo + pidx.counts[s]
            seg = pidx.dst[lo:hi]
            w[lo:hi] = np.where(seg == t, zero, w[lo:hi])
        xw = pidx.xw
        if len(pidx.xsrc):
            hit = np.zeros(len(pidx.xsrc), bool)
            for s, t in zip(dsrc, ddst):
                hit |= (pidx.xsrc == s) & (pidx.xdst == t)
            xw = np.where(hit, zero, pidx.xw)
        _csr_store(child,
                   _CsrIndex(pidx.counts, pidx.starts, pidx.src,
                             pidx.dst, w, pidx.xsrc, pidx.xdst, xw),
                   transpose)


def _batched_frontier_fixpoint(edges, init, max_iters, *, warm=None):
    """Host worklist mode for a (B, n) init: one worklist per source.

    The frontier representation is inherently per-source (each row has
    its own changed-tuple set), so batching is a host loop; the batched
    hot path is ``mode="jit"``.  Returns stacked results, a (B,) iters
    vector, and the per-source FrontierStats list.
    """
    ys, iters, stats = [], [], []
    rows = (np.asarray(init) if warm is None
            else zip(np.asarray(warm[0]), np.asarray(warm[1])))
    for row in rows:
        y, _, it, st = _frontier_fixpoint(
            edges, None if warm is not None else row, max_iters,
            warm=row if warm is not None else None)
        ys.append(y)
        iters.append(it)
        stats.append(st)
    return jnp.stack(ys), np.asarray(iters, np.int32), stats


def _frontier_chunk(edges, y0, d0, it0, budget: int):
    """Budgeted worklist rounds over a ``(B, n)`` carry — the frontier
    runner's ``run_chunk`` body.  One worklist per row (the frontier
    representation is inherently per-source); per-row iteration counting
    matches the staged chunk exactly (a row only counts rounds in which
    its Δ was live)."""
    y0 = np.asarray(y0)
    d0 = np.asarray(d0)
    it0 = np.asarray(it0, np.int32)
    ys, ds, its = [], [], []
    for j in range(y0.shape[0]):
        y, d, it, _ = _frontier_fixpoint(edges, None, budget,
                                         warm=(y0[j], d0[j]))
        ys.append(np.asarray(y))
        ds.append(np.asarray(d))
        its.append(int(it0[j]) + it)
    return np.stack(ys), np.stack(ds), np.asarray(its, np.int32)


def _frontier_fixpoint(edges: SparseRelation, init, max_iters: int, *,
                       warm=None):
    sr = sr_mod.get(edges.semiring, lib="np")
    idx = csr_index(edges)
    counts, starts = idx.counts, idx.starts
    dst, w = idx.dst, idx.w
    n_out = edges.shape[1]

    zero = np.asarray(sr.zero, sr.dtype)
    if warm is None:
        x0 = np.full(n_out, sr.zero, sr.dtype)
        y = x0.copy()
        d = sr.minus(np.asarray(init, sr.dtype), x0)  # δ of constant term
    else:
        y = np.asarray(warm[0], sr.dtype).copy()
        d = np.asarray(warm[1], sr.dtype)

    stats = FrontierStats([], [])
    iters = 0
    live = d != zero if sr.name != "bool" else d
    while bool(live.any()) and iters < max_iters:
        frontier = np.flatnonzero(live)
        dvals = d[frontier]
        y = sr.add(y, d)                       # Y ← Y ⊕ Δ
        # δF(Δ): expand only the frontier's adjacency rows
        deg = counts[frontier]
        rep = np.repeat(np.arange(len(frontier)), deg)
        derived = np.full(n_out, sr.zero, sr.dtype)
        if len(rep):
            run_off = np.arange(len(rep)) - np.repeat(
                np.concatenate([[0], np.cumsum(deg)[:-1]]), deg)
            esel = starts[frontier[rep]] + run_off
            cand_dst = dst[esel]
            cand_val = sr.mul(dvals[rep], w[esel])
            _combine_at(sr.name, derived, cand_dst, cand_val)
        expanded = len(rep)
        if len(idx.xsrc):
            # the unsorted apply_delta overlay: scan is O(nnz(Δ)) / round
            m = live[idx.xsrc]
            if m.any():
                _combine_at(sr.name, derived, idx.xdst[m],
                            sr.mul(d[idx.xsrc[m]], idx.xw[m]))
                expanded += int(m.sum())
        d = sr.minus(derived, y)               # Δ ← δF(Δ) ⊖ (Y ⊕ Δ)
        stats.frontier_sizes.append(int(len(frontier)))
        stats.edges_expanded.append(expanded)
        live = d != zero if sr.name != "bool" else d
        iters += 1
    # (y, d) at loop exit is an exact resumable carry: y is the updated
    # pre-fixpoint and d its still-pending delta — zero when converged
    return jnp.asarray(y), d, iters, stats


def _combine_at(sr_name: str, out: np.ndarray, idx, vals) -> None:
    sr_mod.NP_COMBINE[sr_name].at(out, idx, vals)
