"""Slot pools: persistent batched fixpoints with per-row admit/evict.

The continuous-batching core (DESIGN.md §7).  A :class:`SlotPool` owns
one live ``(B, n)`` GSN carry for a (family, B-bucket) pair.  Instead of
packing a batch, running it to *global* convergence, and answering —
the packed-FIFO shape, whose makespan is the slowest row's — the pool:

* **admits** a queued source into a free slot by splicing its ``init``
  column into the live carry (``y_row ← 0̄``, ``Δ_row ← init ⊖ 0̄`` — the
  cold GSN seed; rows are independent under the per-row masks, so a
  spliced row's trajectory is bit-identical to its single-source run);
* **steps** the whole carry a bounded number of iterations (one chunk);
* **harvests** rows whose per-row convergence mask fired — their answers
  leave immediately and their slots free up for the next admission.

Three interchangeable chunk steppers implement the same GSN body:

* :class:`JaxChunkStepper` — the general path: a jitted
  ``resume_fixpoint_chunk`` (one SpMM per round, chunked
  ``lax.while_loop``; for 𝔹 the packed pull round), compiled once per
  ``(plan.signature, B-bucket, D)`` exactly like the packed server's
  runners.  Its carry lives on the device for the pool's whole life:
  admissions are spliced in by one small program per chunk, and only
  the admitted rows, the per-lane live mask and rounds, and the rows of
  converged lanes cross the host link (a 𝔹 row bit-packed).
* :class:`BitsetBoolStepper` — boolean semiring on CPU: the B query
  lanes live as bits of ``⌈B/64⌉`` uint64 words per vertex, and a round
  is the fused kernel's packed-𝔹 advance
  (:func:`repro.kernels.coo_spmm.bool_round_packed` — one
  ``bitwise_or.reduceat`` over dst-sorted edges) — 64 frontier advances
  per word-op, no XLA scatter.  ~25× the (B, n) SpMM's round
  throughput at B=64 on the 50k power-law serving graph.
* :class:`LevelSyncTropStepper` — tropical semiring with small positive
  *integer* weights on CPU: min-plus distances are computed as
  level-synchronous BFS over the weight-expanded graph (an edge of
  weight w advances a frontier by w levels), again as lane-bitsets with
  one reduceat per weight class per level.  Exact: every reachable
  distance is an integer ≤ levels walked, recovered as
  ``settle_level - admit_level`` and cast to the operator's dtype.

Stepper *selection* is a pool-construction concern
(:func:`build_stepper`); per-request applicability is an admission
concern (``admit`` may refuse an init shape the kernel cannot encode —
e.g. a tropical init with finite non-zero entries — and the scheduler
serves that request through the fallback path instead).

Iteration counts: the jax and bitset steppers count exact GSN rounds
(identical to the single-source runner); the level-sync stepper counts
BFS levels, which is its natural round unit — ``QueryRequest.iters`` is
informational either way.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import semiring as sr_mod
from repro.serve.family import Family, QueryRequest
from repro.sparse.coo import SparseRelation
from repro.trace import span

#: level-sync admissibility: weights must be positive integers ≤ this
#: (the ring buffer holds wmax+1 frontier levels; huge weights would
#: also walk absurd level counts — the jax stepper handles those)
TROP_WMAX_CAP = 64

_INF32 = np.uint32(0xFFFFFFFF)


def _dst_sorted(edges: SparseRelation, select=None):
    """Destination-sorted COO view + unique-dst segment starts, the
    ``reduceat`` geometry shared by both host kernels."""
    eh = edges.as_np()
    k = int(eh.nnz)
    src = eh.coords[:k, 0].astype(np.int64)
    dst = eh.coords[:k, 1].astype(np.int64)
    w = eh.values[:k]
    if select is not None:
        src, dst, w = src[select], dst[select], w[select]
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    udst, seg = np.unique(dst, return_index=True)
    return src, udst, seg, w[order]


def _lane_bits(words: np.ndarray, b: int) -> np.ndarray:
    """(…, W) uint64 words → (…, b) bool lanes."""
    return np.unpackbits(words.view(np.uint8), axis=-1,
                         bitorder="little")[..., :b].astype(bool)


class BitsetBoolStepper:
    """Boolean GSN rounds over lane-bitset state (CPU host kernel).

    Geometry and the per-round advance both delegate to
    :mod:`repro.kernels.coo_spmm`: the pool's rounds are exactly the
    fused kernel's packed-𝔹 path (``bool_round_packed`` over the shared
    dst-sorted :class:`~repro.kernels.coo_spmm.SpmmPlan`), so the serve
    hot loop and the planner-priced backend cannot drift apart.
    """

    #: the carry never leaves the host
    carry_bytes = rows_in = rows_out = 0
    #: every round is a bit-packed pull over dst-sorted edges
    packed = True

    def __init__(self, edges: SparseRelation, n: int, b: int,
                 geom_cache: dict | None = None):
        if edges.semiring != "bool":
            raise ValueError("bitset stepper is boolean-only")
        from repro.kernels import coo_spmm
        self.n, self.b = n, b
        self.w = (b + 63) // 64
        cache = geom_cache if geom_cache is not None else {}
        key = ("spmm_plan", "fused")
        plan = cache.get(key)
        if plan is None:
            plan = cache[key] = coo_spmm.plan_geometry(edges,
                                                       transpose=True)
        self._plan = plan
        self._round = coo_spmm.bool_round_packed
        self.y = np.zeros((n, self.w), np.uint64)
        self.d = np.zeros((n, self.w), np.uint64)
        self.it = np.zeros(b, np.int64)

    def admit(self, j: int, init: np.ndarray) -> bool:
        wj, bit = divmod(j, 64)
        col = np.asarray(init, bool).astype(np.uint64) << np.uint64(bit)
        self.y[:, wj] &= ~np.uint64(1 << bit)
        self.d[:, wj] = (self.d[:, wj] & ~np.uint64(1 << bit)) | col
        self.it[j] = 0
        return True

    def live_lanes(self) -> np.ndarray:
        return _lane_bits(np.bitwise_or.reduce(self.d, axis=0), self.b)

    def frontier_nnz(self) -> int:
        return int(np.unpackbits(self.d.view(np.uint8)).sum())

    def step(self, k: int) -> None:
        for _ in range(k):
            live = self.live_lanes()
            if not live.any():
                return
            self.it += live
            self.y |= self.d
            self.d = self._round(self._plan, self.d) & ~self.y

    def extract(self, j: int) -> tuple[np.ndarray, int]:
        wj, bit = divmod(j, 64)
        one = np.uint64(1 << bit)
        return (self.y[:, wj] & one).astype(bool), int(self.it[j])

    def release(self, j: int) -> None:
        wj, bit = divmod(j, 64)
        mask = ~np.uint64(1 << bit)
        self.y[:, wj] &= mask
        self.d[:, wj] &= mask


class LevelSyncTropStepper:
    """Min-plus distances as level-synchronous bitset BFS (CPU kernel).

    Raises ``ValueError`` at construction when the operator's weights
    are not positive integers ≤ :data:`TROP_WMAX_CAP` — selection then
    falls back to the jax stepper.
    """

    carry_bytes = rows_in = rows_out = 0
    packed = False

    def __init__(self, edges: SparseRelation, n: int, b: int,
                 geom_cache: dict | None = None):
        if edges.semiring != "trop":
            raise ValueError("level-sync stepper is tropical-only")
        self.n, self.b = n, b
        self.w = (b + 63) // 64
        cache = geom_cache if geom_cache is not None else {}
        geom = cache.get("trop_geom")
        if geom is None:
            eh = edges.as_np()
            vals = eh.values[:int(eh.nnz)]
            if len(vals) and (not np.all(vals == np.round(vals))
                              or vals.min() < 1
                              or vals.max() > TROP_WMAX_CAP):
                raise ValueError("level-sync needs positive integer "
                                 f"weights ≤ {TROP_WMAX_CAP}")
            wmax = int(vals.max()) if len(vals) else 1
            iw = vals.astype(np.int64)
            classes = []
            for wc in range(1, wmax + 1):
                sel = np.flatnonzero(iw == wc)
                classes.append(_dst_sorted(edges, sel)[:3]
                               if len(sel) else None)
            geom = cache["trop_geom"] = (vals.dtype, wmax, classes)
        self.dtype, self.wmax, self._classes = geom
        self.ring = np.zeros((self.wmax + 1, n, self.w), np.uint64)
        self.settled = np.zeros((n, self.w), np.uint64)
        # (b, n): lane-major so extract/release touch one contiguous row
        self.dist = np.full((b, n), _INF32, np.uint32)
        self.admit_level = np.zeros(b, np.int64)
        self.level = 0
        self.it = np.zeros(b, np.int64)

    def admit(self, j: int, init: np.ndarray) -> bool:
        init = np.asarray(init)
        finite = np.isfinite(init)
        if finite.any() and init[finite].any():
            return False  # only 0/∞ inits encode as a level-0 frontier
        wj, bit = divmod(j, 64)
        one = np.uint64(1 << bit)
        col = finite.astype(np.uint64) << np.uint64(bit)
        self.ring[self.level % (self.wmax + 1), :, wj] |= col
        self.settled[:, wj] |= col
        self.dist[j, finite] = np.uint32(self.level)
        self.admit_level[j] = self.level
        self.it[j] = 0
        return True

    def live_lanes(self) -> np.ndarray:
        any_front = np.bitwise_or.reduce(
            np.bitwise_or.reduce(self.ring, axis=0), axis=0)
        return _lane_bits(any_front, self.b)

    def frontier_nnz(self) -> int:
        front = np.bitwise_or.reduce(self.ring, axis=0)
        return int(np.unpackbits(front.view(np.uint8)).sum())

    def step(self, k: int) -> None:
        r = self.wmax + 1
        for _ in range(k):
            live = self.live_lanes()
            if not live.any():
                return
            self.it += live
            self.level += 1
            t = self.level
            new = np.zeros((self.n, self.w), np.uint64)
            for wc in range(1, self.wmax + 1):
                cls = self._classes[wc - 1]
                if cls is None or t - wc < 0:
                    continue
                src, udst, seg = cls
                new[udst] |= np.bitwise_or.reduceat(
                    self.ring[(t - wc) % r][src], seg, axis=0)
            new &= ~self.settled
            self.ring[t % r] = new
            rows = np.flatnonzero(new.any(axis=1))
            if len(rows):
                self.settled |= new
                # scatter only the (vertex, lane) pairs that settled
                # this level — a dense where() over dist[rows] gathers
                # and rewrites 64 lanes per row, ~10× the traffic
                r_idx, l_idx = np.nonzero(_lane_bits(new[rows], self.b))
                self.dist[l_idx, rows[r_idx]] = np.uint32(t)

    def extract(self, j: int) -> tuple[np.ndarray, int]:
        col = self.dist[j]
        out = col.astype(np.float64) - self.admit_level[j]
        out[col == _INF32] = np.inf
        return out.astype(self.dtype), int(self.it[j])

    def release(self, j: int) -> None:
        wj, bit = divmod(j, 64)
        mask = ~np.uint64(1 << bit)
        # no ring sweep: a releasable lane converged, i.e. has no
        # frontier bits anywhere in the ring by definition
        self.settled[:, wj] &= mask
        self.dist[j] = _INF32


@functools.partial(jax.jit, static_argnames=("zero",),
                   donate_argnums=(0, 1, 2))
def _splice(y, d, it, idx, rows, *, zero):
    """Splice staged rows into the carry in place: ``y_row ← 0̄``,
    ``Δ_row ← rows[i]``, ``it_row ← 0`` for each lane ``idx[i]``.
    ``idx`` is padded to B with out-of-range lanes, which are dropped;
    ``rows`` holds B row buffers (bit-packed uint8 for a bool carry).
    Row selects and ``where``s over the carry, no scatter into it."""
    b = it.shape[0]
    lane = jnp.full(b, b, jnp.int32).at[idx].set(
        jnp.arange(b, dtype=jnp.int32), mode="drop")
    new = (lane < b)[:, None]
    rows = jnp.stack(rows)[jnp.minimum(lane, b - 1)]
    if d.dtype == jnp.bool_:
        rows = jnp.unpackbits(rows, axis=1, count=d.shape[1]).astype(bool)
    y = jnp.where(new, jnp.asarray(zero, y.dtype), y)
    d = jnp.where(new, rows.astype(d.dtype), d)
    it = jnp.where(new[:, 0], 0, it)
    return y, d, it


@functools.partial(jax.jit, static_argnames=("zero",))
def _live(d, *, zero):
    """Per-lane liveness: any entry of the lane's Δ row off 0̄."""
    return jnp.any(d != jnp.asarray(zero, d.dtype), axis=1)


@functools.partial(jax.jit, static_argnames=("zero",))
def _nnz(d, *, zero):
    return jnp.sum(d != jnp.asarray(zero, d.dtype), dtype=jnp.int32)


@jax.jit
def _gather(y, idx):
    """The rows of ``idx`` (padded to B), a bool carry's bit-packed, as
    B separate buffers, so the host copies only the rows it asks for."""
    rows = y[idx]
    if rows.dtype == jnp.bool_:
        rows = jnp.packbits(rows, axis=1)
    return tuple(rows[i] for i in range(rows.shape[0]))


class JaxChunkStepper:
    """The general chunk stepper: a device-resident (B, n) carry
    advanced by a jitted bounded slice of the batched GSN loop.

    ``y``, ``d`` and ``it`` stay on the device for the pool's life; only
    rows and the live mask cross the host link.  ``admit`` stages a row
    on the host and the next chunk splices every staged row in one call;
    after each chunk one reduction over Δ brings back the live mask, and
    the rows of lanes that converged are copied out (a bool row
    bit-packed).  ``packed`` says whether the chunk's rounds take the
    packed pull round, as a :class:`~repro.sparse.fixpoint.CompiledChunk`
    reports it."""

    def __init__(self, edges: SparseRelation, n: int, b: int,
                 chunk_fn):
        self.edges = edges
        self.n, self.b = n, b
        self._chunk = chunk_fn          # (edges, y, d, it) -> (y, d, it)
        packs = getattr(chunk_fn, "packs", None)
        self.packed = packs is not None and packs(edges)
        sr = sr_mod.get(edges.semiring, lib="np")
        self._sr = sr
        self._zero = np.asarray(sr.zero, sr.dtype).item()
        self._bool = np.dtype(sr.dtype) == np.bool_
        self.y = jnp.full((b, n), sr.zero, sr.dtype)
        self.d = jnp.full((b, n), sr.zero, sr.dtype)
        self.it_dev = jnp.zeros(b, jnp.int32)
        # host mirrors: per-lane liveness and rounds, as of the last chunk
        # or admission
        self.live = np.zeros(b, bool)
        self.it = np.zeros(b, np.int32)
        self._occupied = np.zeros(b, bool)
        self._staged: dict[int, np.ndarray] = {}
        self._out: dict[int, np.ndarray] = {}
        width = -(-n // 8) if self._bool else n
        self._pad = jnp.zeros(width, np.uint8 if self._bool else sr.dtype)
        self.carry_bytes = 0
        self.rows_in = 0
        self.rows_out = 0

    def admit(self, j: int, init: np.ndarray) -> bool:
        # the cold GSN seed: d0 = (init ⊕ 0̄⊗E) ⊖ 0̄ = init ⊖ 0̄
        zero_row = np.full(self.n, self._sr.zero, self._sr.dtype)
        d = self._sr.minus(np.asarray(init, self._sr.dtype), zero_row)
        self.live[j] = bool((d != zero_row).any())
        self._staged[j] = np.packbits(d) if self._bool else d
        self._occupied[j] = True
        self.it[j] = 0
        return True

    def live_lanes(self) -> np.ndarray:
        return self.live.copy()

    def frontier_nnz(self) -> int:
        """Live Δ entries, counted on the device on demand; a staged row
        replaces a converged lane's 0̄ row, so it adds its own count."""
        staged = sum(int(np.unpackbits(r).sum()) if self._bool
                     else int((r != self._zero).sum())
                     for r in self._staged.values())
        return int(_nnz(self.d, zero=self._zero)) + staged

    def _upload(self) -> int:
        """Splice every staged row in one call; the bytes sent up."""
        if not self._staged:
            return 0
        k = len(self._staged)
        idx = np.full(self.b, self.b, np.int32)
        idx[:k] = list(self._staged)
        staged = list(self._staged.values())
        moved = idx.nbytes + sum(r.nbytes for r in staged)
        # every row a device buffer, the pad rows resident: one program
        # for every admission count
        rows = tuple(jax.device_put(staged)) + (self._pad,) * (self.b - k)
        self.rows_in += k
        self._staged.clear()
        # blocked, so the upload span covers the splice and pool.run
        # only the chunk
        self.y, self.d, self.it_dev = jax.block_until_ready(_splice(
            self.y, self.d, self.it_dev, idx, rows, zero=self._zero))
        return moved

    def _download(self) -> int:
        """Bring back the live mask and the rounds, then copy out the
        rows of occupied lanes that converged; the bytes moved."""
        live, it = jax.device_get((_live(self.d, zero=self._zero),
                                   self.it_dev))
        self.live, self.it = np.array(live), np.array(it, np.int32)
        moved = self.live.nbytes + self.it.nbytes
        done = [int(j) for j in np.flatnonzero(self._occupied & ~self.live)
                if j not in self._out]
        if done:
            idx = np.zeros(self.b, np.int32)
            idx[:len(done)] = done
            rows = jax.device_get(_gather(self.y, idx)[:len(done)])
            self._out.update(zip(done, rows))
            self.rows_out += len(done)
            moved += idx.nbytes + sum(r.nbytes for r in rows)
        return moved

    def step(self, k: int) -> None:
        """One chunk.  ``carry_bytes`` counts the bytes it moved between
        host and device: edges not already on the device, the staged
        rows and their index, the live mask and rounds, and the copied
        out rows and their index."""
        with span("pool.scan"):
            if not self.live.any():
                return
        with span("pool.upload"):
            edges = self.edges
            moved = sum(np.asarray(x).nbytes
                        for x in (edges.coords, edges.values, edges.nnz)
                        if not isinstance(x, jax.Array))
            edges = edges.as_jnp()
            moved += self._upload()
        with span("pool.run"):
            self.y, self.d, self.it_dev = jax.block_until_ready(
                self._chunk(edges, self.y, self.d, self.it_dev))
        with span("pool.download"):
            moved += self._download()
        self.carry_bytes += moved

    def extract(self, j: int) -> tuple[np.ndarray, int]:
        """Lane ``j``'s answer as a writable host row.  A staged lane's
        is 0̄ (its splice is pending); a converged lane's was copied out
        by the chunk that saw it converge."""
        if j in self._staged:
            return np.full(self.n, self._sr.zero, self._sr.dtype), 0
        row = self._out[j]
        if self._bool:
            return np.unpackbits(row, count=self.n).astype(bool), \
                int(self.it[j])
        return np.array(row), int(self.it[j])

    def release(self, j: int) -> None:
        """Host bookkeeping only: a converged lane's Δ row is already 0̄,
        and the next admission overwrites its y row."""
        self._occupied[j] = False
        self._staged.pop(j, None)
        self._out.pop(j, None)


def build_stepper(fam: Family, b: int, *, host_kernels: bool,
                  chunk_fn_factory):
    """Pick the cheapest applicable stepper for this family's operator.

    ``chunk_fn_factory()`` lazily supplies the compiled jax chunk
    function (so host-kernel pools never touch the compile cache).
    """
    edges = fam.edges
    if not isinstance(edges, SparseRelation):
        raise ValueError("slot pools need a sparse linear operator")
    if host_kernels and jax.default_backend() == "cpu":
        if edges.semiring == "bool":
            return BitsetBoolStepper(edges, fam.n, b,
                                     geom_cache=fam.kernel_cache)
        if edges.semiring == "trop":
            try:
                return LevelSyncTropStepper(edges, fam.n, b,
                                            geom_cache=fam.kernel_cache)
            except ValueError:
                pass
    return JaxChunkStepper(edges, fam.n, b, chunk_fn_factory())


@dataclasses.dataclass
class _Slot:
    req: QueryRequest | None = None


class SlotPool:
    """Occupancy bookkeeping around one chunk stepper."""

    def __init__(self, fam: Family, b: int, *, host_kernels: bool,
                 chunk_fn_factory):
        self.fam = fam
        self.b = b
        self.stepper = build_stepper(fam, b, host_kernels=host_kernels,
                                     chunk_fn_factory=chunk_fn_factory)
        self.slots: list[QueryRequest | None] = [None] * b
        self._free: list[int] = list(range(b))[::-1]

    @property
    def occupied(self) -> int:
        return self.b - len(self._free)

    @property
    def free_slots(self) -> int:
        return len(self._free)

    def admit(self, req: QueryRequest, init: np.ndarray) -> bool:
        """Splice ``init`` into a free slot; False when the stepper
        cannot encode this init (caller serves it another way) or the
        pool is full."""
        if not self._free:
            return False
        j = self._free[-1]
        if not self.stepper.admit(j, init):
            return False
        self._free.pop()
        self.slots[j] = req
        return True

    def step(self, k: int) -> dict[str, int]:
        """Step one chunk of at most ``k`` rounds; returns its counter
        increments: the rounds the device ran, those of them that took
        the packed pull round, the carry bytes moved between host and
        device, and the rows spliced in and copied out.  Rounds are the
        most any lane advanced: a lane whose Δ row is 0̄ stays 0̄, so the
        lane live in the last round was live in every round."""
        st = self.stepper
        before = (st.it.copy(), st.carry_bytes, st.rows_in, st.rows_out)
        st.step(k)
        rounds = int((st.it - before[0]).max(initial=0))
        return {"rounds": rounds,
                "packed_rounds": rounds if st.packed else 0,
                "carry_bytes": st.carry_bytes - before[1],
                "rows_in": st.rows_in - before[2],
                "rows_out": st.rows_out - before[3]}

    def frontier_nnz(self) -> int:
        """Live Δ entries across all lanes, on demand
        (``stats()["families"][f]["frontier_nnz"]``)."""
        return self.stepper.frontier_nnz()

    def harvest(self) -> list[tuple[QueryRequest, np.ndarray, int]]:
        """Evict every occupied slot whose convergence mask fired:
        extract its answer, free the slot."""
        live = self.stepper.live_lanes()
        out = []
        for j, req in enumerate(self.slots):
            if req is None or live[j]:
                continue
            y, iters = self.stepper.extract(j)
            self.stepper.release(j)
            self.slots[j] = None
            self._free.append(j)
            out.append((req, y, iters))
        return out
