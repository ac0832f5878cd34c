"""Fused batched COO semiring SpMM: gather → ⊗ → segment-⊕ in one pass.

The serving hot loop is ``d' = d ⊗ E`` — a batched semiring SpMM inside
``lax.while_loop`` (DESIGN.md §3).  Composed from generic jnp ops it
makes three memory passes per iteration (gather rows, multiply, scatter
rows); this module fuses them into a single sweep over *edge tiles*, in
two executions sharing one host-planned geometry:

* **Pallas TPU kernel** (:func:`spmm_pallas`) — the scalar-prefetch
  block-mapping pattern of ``kernels/coo_segment.py`` extended to a
  second sparse axis: edges are bucketed by (output block, gather block)
  so each grid step touches one ``(bs, B)`` x-tile and one ``(bn, B)``
  output tile, both resident in VMEM.  ⊕/⊗ bodies are specialized per
  semiring: bool/nat/real lower gather and scatter to one-hot f32
  matmuls on the MXU (bool is or-counted and thresholded on exit);
  trop/maxplus use masked select + min/max reduces on the VPU.
* **Host fused executor** (:func:`spmm_host`, :func:`bool_round_packed`)
  — the CPU serving backend.  For 𝔹 the B query lanes are bit-packed
  into uint64 words (PR 7's payload layout) and one round is a single
  ``np.bitwise_or.reduceat`` over dst-sorted edges: ~64× fewer bytes
  than the (nnz, B) boolean gather/scatter, measured 27× per-iteration
  at the 50k-vertex serve shape (BENCH_kernels.json).  Other semirings
  get a generic dst-sorted ``ufunc.reduceat`` fallback.

Geometry (:func:`plan_geometry`) is host-built from the *concrete*
operator and weakref-cached per (coords, values, transpose) — the same
discipline as the frontier fixpoint's CSR cache.  It is deliberately not
traceable: the chunk capacity depends on the edge distribution, so the
fused backends require a concrete operator (callers under jit close over
it; see ``planner.compile_batched``).

Oracle: ``sparse/contract.py``'s jnp path; parity is tested in interpret
mode across semirings, ragged nnz tails, batching, and transpose.
"""

from __future__ import annotations

import dataclasses
import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import semiring as sr_mod

#: ⊕-identity used for pad slots and tile init (f32 compute).
_PAD = {"bool": 0.0, "nat": 0.0, "real": 0.0,
        "trop": float("inf"), "maxplus": float("-inf")}

#: semirings whose ⊕/⊗ lower to (+, ×) on one-hot f32 operands — these
#: run gather and scatter as MXU matmuls; the rest take the VPU
#: select-reduce body (min/max has no matmul form).
_DOT = ("bool", "nat", "real")

#: (bk edges/chunk, bs gather rows, bn output rows).  The dot family
#: amortizes one-hot matmuls over big tiles; the select-reduce family
#: unrolls one select per gather row and per output row, so its tiles
#: stay small.
_BLOCKS = {"dot": (256, 256, 128), "minmax": (32, 32, 32)}


def _family(sr_name: str) -> str:
    return "dot" if sr_name in _DOT else "minmax"


@dataclasses.dataclass
class SpmmPlan:
    """Host-planned geometry for one (operator, transpose) orientation.

    The dst-sorted arrays serve the host executors directly; the Pallas
    chunk tiles are built lazily on first kernel use.  ``jit_cache``
    holds per-plan compiled closures (fixpoint/chunk runners) so serving
    families re-enter compiled code across calls.
    """

    sr_name: str
    n_in: int
    n_out: int
    transpose: bool
    nnz: int
    src: np.ndarray    # (nnz,) gather index per edge, dst-sorted
    dst: np.ndarray    # (nnz,) output index per edge, sorted
    udst: np.ndarray   # unique output indices
    seg: np.ndarray    # reduceat segment starts into src/dst
    w: np.ndarray      # (nnz,) edge values, semiring dtype
    bk: int
    bs: int
    bn: int
    chunks: tuple | None = None
    jit_cache: dict = dataclasses.field(default_factory=dict)


_PLANS: dict[tuple[int, int, bool], tuple[object, object, SpmmPlan]] = {}


def plan_geometry(rel, *, transpose: bool = False) -> SpmmPlan:
    """The (cached) fused-SpMM geometry of a binary sparse relation."""
    if isinstance(rel.coords, jax.core.Tracer) or \
            isinstance(rel.values, jax.core.Tracer):
        raise ValueError(
            "fused SpMM needs a concrete operator (its edge-tile geometry "
            "is host-built); keep backend='jnp' under tracing or close "
            "over the operator as a constant")
    key = (id(rel.coords), id(rel.values), bool(transpose))
    ent = _PLANS.get(key)
    if ent is not None and ent[0]() is rel.coords \
            and ent[1]() is rel.values:
        return ent[2]
    plan = _build_plan(rel, transpose)

    def _evict(ref, k=key):
        cur = _PLANS.get(k)
        if cur is not None and ref in (cur[0], cur[1]):
            _PLANS.pop(k, None)

    try:
        _PLANS[key] = (weakref.ref(rel.coords, _evict),
                       weakref.ref(rel.values, _evict), plan)
    except TypeError:  # pragma: no cover — all our buffers are weakrefable
        pass
    return plan


def _build_plan(rel, transpose: bool) -> SpmmPlan:
    h = rel.as_np()
    k = int(h.nnz)
    ci, co = (0, 1) if transpose else (1, 0)
    gidx = np.asarray(h.coords[:k, ci], np.int64)
    oidx = np.asarray(h.coords[:k, co], np.int64)
    vals = np.asarray(h.values[:k])
    order = np.argsort(oidx, kind="stable")
    src, dst, w = gidx[order], oidx[order], vals[order]
    if k:
        udst, seg = np.unique(dst, return_index=True)
    else:
        udst, seg = np.zeros(0, np.int64), np.zeros(0, np.int64)
    bk, bs, bn = _BLOCKS[_family(rel.semiring)]
    return SpmmPlan(rel.semiring, int(h.shape[ci]), int(h.shape[co]),
                    transpose, k, src, dst, udst, seg, w, bk, bs, bn)


# ---------------------------------------------------------------------------
# Pallas kernel


def padded_slots(rel, *, transpose: bool = True) -> int:
    """Edge slots of the Pallas chunk geometry, counted without building
    it: every non-empty (out block, src block) bucket rounds up to whole
    ``bk``-slot chunks and every empty out block takes one all-pad chunk
    (:func:`_build_chunks`).  The kernel's per-round work and its
    geometry's bytes both scale with this count, not with nnz."""
    h = rel.as_np()
    k = int(h.nnz)
    ci, co = (0, 1) if transpose else (1, 0)
    bk, bs, bn = _BLOCKS[_family(rel.semiring)]
    nsb = max(1, -(-int(h.shape[ci]) // bs))
    ndb = max(1, -(-int(h.shape[co]) // bn))
    key = (np.asarray(h.coords[:k, co], np.int64) // bn) * nsb \
        + np.asarray(h.coords[:k, ci], np.int64) // bs
    ub, cnt = np.unique(key, return_counts=True)
    empty = ndb - len(np.unique(ub // nsb))
    return int((np.sum(-(-cnt // bk)) + empty) * bk)


def _chunk_geometry(plan: SpmmPlan) -> tuple:
    if plan.chunks is None:
        plan.chunks = _build_chunks(plan)
    return plan.chunks


def _build_chunks(plan: SpmmPlan) -> tuple:
    """Pack edges into (bk,) chunk rows bucketed by (out block, src block).

    Chunks never straddle a bucket, so each grid step reads exactly one
    x-tile and accumulates into exactly one output tile; buckets are
    out-block-major, so every output tile's chunks are consecutive in
    grid order (the Pallas revisit-accumulate contract).  Every output
    block gets at least one chunk — an all-pad one if no edge lands in
    it — so its tile is still initialized to 0̄.
    """
    bk, bs, bn = plan.bk, plan.bs, plan.bn
    nsb = max(1, -(-plan.n_in // bs))
    ndb = max(1, -(-plan.n_out // bn))
    ob = plan.dst // bn
    gb = plan.src // bs
    order = np.lexsort((gb, ob))
    g_s, o_s = plan.src[order], plan.dst[order]
    v_s = np.asarray(plan.w[order], np.float32)
    key = ob[order] * nsb + gb[order]
    ub, bstart, bcnt = np.unique(key, return_index=True, return_counts=True)
    present = np.zeros(ndb, bool)
    if len(ub):
        present[ub // nsb] = True
    missing = np.flatnonzero(~present).astype(np.int64)
    keys = np.concatenate([ub, missing * nsb])
    cnts = np.concatenate([bcnt, np.zeros(len(missing), np.int64)])
    bord = np.argsort(keys, kind="stable")
    keys, cnts = keys[bord], cnts[bord]
    rank = np.empty(len(bord), np.int64)
    rank[bord] = np.arange(len(bord))
    erank = rank[:len(ub)]                        # ub position → bucket rank
    nchunks = np.maximum(1, -(-cnts // bk))
    cstart = np.concatenate([[0], np.cumsum(nchunks)[:-1]]).astype(np.int64)
    c_total = int(cstart[-1] + nchunks[-1])
    dblk = np.repeat(keys // nsb, nchunks).astype(np.int32)
    sblk = np.repeat(keys % nsb, nchunks).astype(np.int32)
    first = np.ones(c_total, np.int32)
    first[1:] = (dblk[1:] != dblk[:-1]).astype(np.int32)
    # pad slots: loc = block size ⇒ one-hot all-miss on both axes, value
    # = ⊕-identity — they contribute nothing on either kernel body
    # (c_total, 1, bk): one chunk row per grid step whose last two
    # block dims equal the array's, as Mosaic requires of a block that
    # is not (8, 128)-aligned
    locs = np.full((c_total, 1, bk), bs, np.int32)
    locd = np.full((c_total, 1, bk), bn, np.int32)
    vbuf = np.full((c_total, 1, bk), _PAD[plan.sr_name], np.float32)
    if plan.nnz:
        b_of = np.searchsorted(bstart, np.arange(plan.nnz),
                               side="right") - 1
        pos = np.arange(plan.nnz) - bstart[b_of]
        chunk = cstart[erank[b_of]] + pos // bk
        slot = pos % bk
        locs[chunk, 0, slot] = (g_s % bs).astype(np.int32)
        locd[chunk, 0, slot] = (o_s % bn).astype(np.int32)
        vbuf[chunk, 0, slot] = v_s
    # plain numpy on purpose: geometry may be first materialized under an
    # outer trace (the per-operator jitted fixpoints), where jnp.asarray
    # would yield leakable tracers — as np buffers they enter jit as
    # ordinary constants/arguments instead
    return sblk, dblk, first, locs, locd, vbuf, nsb, ndb


def _spmm_kernel(sblk_ref, dblk_ref, first_ref, locs_ref, locd_ref,
                 vals_ref, x_ref, o_ref, *, mode: str, bk: int, bs: int,
                 bn: int):
    c = pl.program_id(0)
    init = _PAD[mode]

    @pl.when(first_ref[c] == 1)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, init)

    locs = locs_ref[0, :]                                 # (bk,) int32
    locd = locd_ref[0, :]                                 # (bk,) int32
    w = vals_ref[0, :]                                    # (bk,) f32
    x = x_ref[...]                                        # (bs, bp) f32
    if mode in _DOT:
        # gather and scatter as one-hot matmuls: g = 1[src] · x on the
        # way in, out += 1[dst]ᵀ · (w ⊙ g) on the way out.  Exact for 𝔹
        # (or-counts thresholded on exit) and small-int ℕ — same f32
        # compute contract as the jnp path.
        src_oh = (locs[:, None] ==
                  jax.lax.broadcasted_iota(jnp.int32, (bk, bs), 1)
                  ).astype(jnp.float32)                   # (bk, bs)
        dst_oh = (jax.lax.broadcasted_iota(jnp.int32, (bn, bk), 0) ==
                  locd[None, :]).astype(jnp.float32)      # (bn, bk)
        g = jnp.dot(src_oh, x, preferred_element_type=jnp.float32)
        p = w[:, None] * g                                # (bk, bp)
        o_ref[...] += jnp.dot(dst_oh, p,
                              preferred_element_type=jnp.float32)
    else:
        red, comb = (jnp.min, jnp.minimum) if mode == "trop" else \
            (jnp.max, jnp.maximum)
        # 2-D selects only: Mosaic has no shape cast for the (bk, bs, B)
        # broadcast form.  Gather one source row at a time (each slot
        # matches at most one row; pad slots match none and keep 0̄) …
        g = jnp.full((bk, x.shape[1]), init, jnp.float32)
        for s in range(bs):
            g = jnp.where(locs[:, None] == s, x[s:s + 1, :], g)
        p = w[:, None] + g                                # ⊗ is +
        # … then ⊕-reduce the slots landing on each output row
        for r in range(bn):
            row = red(jnp.where(locd[:, None] == r, p, init), axis=0,
                      keepdims=True)                      # (1, bp)
            o_ref[r:r + 1, :] = comb(o_ref[r:r + 1, :], row)


@functools.partial(jax.jit,
                   static_argnames=("sr_name", "bk", "bs", "bn", "ndb",
                                    "interpret"))
def _spmm_pallas_call(sblk, dblk, first, locs, locd, vals, xp, *,
                      sr_name: str, bk: int, bs: int, bn: int, ndb: int,
                      interpret: bool):
    c_total, bp = locs.shape[0], xp.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(c_total,),
        in_specs=[
            pl.BlockSpec((None, 1, bk), lambda c, sb, db, fi: (c, 0, 0)),
            pl.BlockSpec((None, 1, bk), lambda c, sb, db, fi: (c, 0, 0)),
            pl.BlockSpec((None, 1, bk), lambda c, sb, db, fi: (c, 0, 0)),
            pl.BlockSpec((bs, bp), lambda c, sb, db, fi: (sb[c], 0)),
        ],
        out_specs=pl.BlockSpec((bn, bp),
                               lambda c, sb, db, fi: (db[c], 0)),
    )
    return pl.pallas_call(
        functools.partial(_spmm_kernel, mode=sr_name, bk=bk, bs=bs, bn=bn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ndb * bn, bp), jnp.float32),
        interpret=interpret,
    )(sblk, dblk, first, locs, locd, vals, xp)


def spmm_pallas(plan: SpmmPlan, x, *, interpret: bool = False):
    """Fused SpMM via the Pallas kernel: x (n_in, B) or (n_in,) → dense.

    Compute runs in f32 with B padded to the 128-lane register width;
    boolean results are thresholded back on exit, matching the jnp
    oracle bit-for-bit.
    """
    sr = sr_mod.get(plan.sr_name)
    sblk, dblk, first, locs, locd, vals, nsb, ndb = _chunk_geometry(plan)
    x = jnp.asarray(x)
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    assert x.shape[0] == plan.n_in, (x.shape, plan.n_in)
    b = x.shape[1]
    bp = max(128, -(-b // 128) * 128)
    xp = jnp.zeros((nsb * plan.bs, bp), jnp.float32)
    xp = xp.at[:plan.n_in, :b].set(x.astype(jnp.float32))
    out = _spmm_pallas_call(sblk, dblk, first, locs, locd, vals, xp,
                            sr_name=plan.sr_name, bk=plan.bk, bs=plan.bs,
                            bn=plan.bn, ndb=ndb, interpret=interpret)
    out = out[:plan.n_out, :b]
    out = out > 0.5 if plan.sr_name == "bool" else out.astype(sr.dtype)
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# Host fused executors (the CPU serving backend)


def pack_lanes(x) -> np.ndarray:
    """(B, n) bool → (n, W) uint64 words: lane b lives in bit b (LE)."""
    x = np.ascontiguousarray(np.asarray(x, bool).T)       # (n, B)
    n, b = x.shape
    w = max(1, -(-b // 64))
    bits = np.packbits(x, axis=1, bitorder="little")      # (n, ceil(b/8))
    buf = np.zeros((n, w * 8), np.uint8)
    buf[:, :bits.shape[1]] = bits
    return buf.view(np.uint64)


def unpack_lanes(words: np.ndarray, b: int) -> np.ndarray:
    """(n, W) uint64 → (B, n) bool — inverse of :func:`pack_lanes`."""
    bits = np.unpackbits(words.view(np.uint8), axis=1, bitorder="little")
    return np.ascontiguousarray(bits[:, :b].T).astype(bool)


def bool_round_packed(plan: SpmmPlan, words: np.ndarray) -> np.ndarray:
    """One fused 𝔹 round over packed lanes: (n_in, W) → (n_out, W).

    All live bool edges carry ⊤ (``from_coo`` drops 0̄), so the round is
    pure gather + or-reduce — a single ``bitwise_or.reduceat`` sweep
    over dst-sorted edges, 64 query lanes per word.
    """
    out = np.zeros((plan.n_out, words.shape[1]), np.uint64)
    if plan.nnz:
        out[plan.udst] = np.bitwise_or.reduceat(
            words[plan.src], plan.seg, axis=0)
    return out


def spmm_host(plan: SpmmPlan, x):
    """Host-numpy fused SpMM: gather → ⊗ → ``ufunc.reduceat`` segment-⊕.

    The generic fallback body for non-𝔹 semirings (and the oracle for
    the packed 𝔹 round); one pass over dst-sorted edges, no scatter.
    """
    srn = sr_mod.get(plan.sr_name, lib="np")
    x = np.asarray(x)
    squeeze = x.ndim == 1
    x2 = x[:, None] if squeeze else x
    assert x2.shape[0] == plan.n_in, (x2.shape, plan.n_in)
    out = np.full((plan.n_out, x2.shape[1]), srn.zero, srn.dtype)
    if plan.nnz:
        prod = srn.mul(plan.w[:, None], x2[plan.src])
        out[plan.udst] = sr_mod.NP_COMBINE[plan.sr_name].reduceat(
            prod, plan.seg, axis=0)
    return out[:, 0] if squeeze else out
