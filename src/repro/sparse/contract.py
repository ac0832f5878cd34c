"""Sparse semiring contraction: SpMV / SpMM / SpMSpM.

The dense engine lowers a binary join-and-aggregate to ``C = A ⊕.⊗ B``
(semiring matmul).  These are the sparse counterparts over a COO
:class:`~repro.sparse.coo.SparseRelation`:

* ``spmv``/``vspm`` — sparse matrix × dense vector (either side): the
  workhorse of frontier fixpoints.  Per edge ``(z, y, w)``: gather the
  vector at the contracted key, ⊗ with the edge value, and ⊕-reduce by the
  output key via :func:`repro.kernels.ops.semiring_segment_reduce`
  (Pallas segment-reduce on TPU, jnp scatter elsewhere).  Cost O(nnz),
  independent of the dense key-space size.
* ``spmm`` — sparse matrix × dense matrix, same scheme with row payloads.
  The bounded fixpoint chunk no longer runs it for 𝔹: there a round is
  a pull of bit-packed words over dst-sorted edges, with no scatter
  (:mod:`repro.sparse.fixpoint`).
* ``spmspm`` — sparse × sparse → sparse, a host/numpy sort-merge join on
  the contracted key (the eager ``backend="np"`` world of the
  synthesizer); on-device callers densify one side instead, since output
  nnz is data-dependent and cannot be bounded statically.

Padding discipline: gathers use ⊗-identity fill and padded values are 0̄,
so padding rows contribute 0̄ ⊗ 1̄ = 0̄ to every reduction; scatters use
``mode="drop"`` on the out-of-range coordinate sentinel.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import semiring as sr_mod
from repro.sparse.coo import SparseRelation


def _gather(x, idx, fill):
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=fill)


def _fused_spmm(rel: SparseRelation, b, *, transpose: bool, backend: str):
    """Route an SpMM through :mod:`repro.kernels.coo_spmm`.

    ``backend="pallas"`` runs the fused Pallas kernel (interpreted off-TPU
    so CI's CPU job exercises the kernel path); ``backend="fused"`` runs
    the host-numpy fused executor.  Both need a *concrete* operator —
    their edge-tile geometry is host-planned and weakref-cached.
    """
    from repro.kernels import coo_spmm, ops as kops
    plan = coo_spmm.plan_geometry(rel, transpose=transpose)
    if backend == "pallas":
        return coo_spmm.spmm_pallas(plan, b,
                                    interpret=kops.pallas_interpret())
    if backend == "fused":
        return coo_spmm.spmm_host(plan, b)
    raise ValueError(f"unknown SpMM backend {backend!r}")


def spmv(rel: SparseRelation, x, *, transpose: bool = False):
    """``out[i] = ⊕_j rel[i, j] ⊗ x[j]`` (or ``⊕_i rel[i,j] ⊗ x[i]`` with
    ``transpose``).  Returns a dense vector over the non-contracted sort."""
    assert rel.arity == 2, rel
    sr = sr_mod.get(rel.semiring)
    from repro.kernels import ops as kops
    contract_ax, out_ax = (0, 1) if transpose else (1, 0)
    gathered = _gather(jnp.asarray(x), rel.coords[:, contract_ax], sr.one)
    prod = sr.mul(rel.values, gathered)
    return kops.semiring_segment_reduce(
        sr, prod, rel.coords[:, out_ax], rel.shape[out_ax])


def vspm(x, rel: SparseRelation):
    """``out[j] = ⊕_i x[i] ⊗ rel[i, j]`` — vector × sparse matrix."""
    return spmv(rel, x, transpose=True)


def spmm(rel: SparseRelation, b, *, transpose: bool = False,
         backend: str = "jnp"):
    """Sparse (n, k) × dense (k, d) → dense (n, d) over the semiring.

    Per edge the gathered payload is a whole row of ``b`` and the
    ⊕-reduction scatters contiguous rows — so with d = B query lanes the
    per-edge index overhead of SpMV is amortized across the batch (the
    mechanism behind the batched multi-source fixpoint, DESIGN.md §3).

    ``backend`` selects the execution: ``"jnp"`` (default, traceable) is
    the gather/⊗/segment-⊕ composition below; ``"pallas"``/``"fused"``
    route through the fused single-pass kernel (DESIGN.md §9) and need a
    concrete operator.
    """
    assert rel.arity == 2 and b.ndim == 2, (rel, b.shape)
    if backend != "jnp":
        return _fused_spmm(rel, b, transpose=transpose, backend=backend)
    sr = sr_mod.get(rel.semiring)
    from repro.kernels import ops as kops
    contract_ax, out_ax = (0, 1) if transpose else (1, 0)
    rows = _gather(jnp.asarray(b), rel.coords[:, contract_ax],
                   sr.one)                                 # (cap, d)
    prod = sr.mul(rel.values[:, None], rows)
    return kops.semiring_segment_reduce(
        sr, prod, rel.coords[:, out_ax], rel.shape[out_ax])


def mspm(x, rel: SparseRelation, *, backend: str = "jnp"):
    """Dense (B, n) × sparse (n, m) → dense (B, m): batched vspm.

    ``out[b, j] = ⊕_i x[b, i] ⊗ rel[i, j]`` — the multi-source frontier
    advance.  Internally runs in the (n, B) layout (`spmm` on the
    transposed orientation) so gathers and scatters move contiguous
    B-wide rows; the transposes at the boundary are free under jit when
    the caller keeps the (n, B) layout (as the batched fixpoint does).
    ``backend`` as in :func:`spmm`.
    """
    x = jnp.asarray(x) if backend != "fused" else np.asarray(x)
    assert x.ndim == 2, x.shape
    return spmm(rel, x.T, transpose=True, backend=backend).T


def spmspm(a: SparseRelation, b: SparseRelation, *,
           capacity: int | None = None) -> SparseRelation:
    """Sparse × sparse → sparse: ``C[i,j] = ⊕_k A[i,k] ⊗ B[k,j]``.

    Host/numpy only (the output's nnz is data-dependent): a sort-merge
    join on k with ⊕-coalescing of the (i, j) results.
    """
    assert a.arity == 2 and b.arity == 2
    assert a.shape[1] == b.shape[0], (a.shape, b.shape)
    assert a.semiring == b.semiring
    sr = sr_mod.get(a.semiring, lib="np")
    ah, bh = a.as_np(), b.as_np()
    ka, kb = int(ah.nnz), int(bh.nnz)
    ai, ak, av = (ah.coords[:ka, 0].astype(np.int64),
                  ah.coords[:ka, 1].astype(np.int64), ah.values[:ka])
    bk, bj, bv = (bh.coords[:kb, 0].astype(np.int64),
                  bh.coords[:kb, 1].astype(np.int64), bh.values[:kb])
    # CSR-index B by its contracted key k
    order = np.argsort(bk, kind="stable")
    bk, bj, bv = bk[order], bj[order], bv[order]
    counts = np.bincount(bk, minlength=a.shape[1])
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    # expand: every A entry joins its run of B entries sharing k
    deg = counts[ak]
    rep = np.repeat(np.arange(ka), deg)
    if len(rep):
        run_off = np.arange(len(rep)) - np.repeat(
            np.concatenate([[0], np.cumsum(deg)[:-1]]), deg)
        bsel = starts[ak[rep]] + run_off
    else:
        bsel = np.zeros(0, np.int64)
    coords = np.stack([ai[rep], bj[bsel]], axis=1) if len(rep) else \
        np.zeros((0, 2), np.int64)
    values = sr.mul(av[rep], bv[bsel]) if len(rep) else \
        np.zeros(0, sr.dtype)
    return SparseRelation.from_coo(
        coords, values, (a.shape[0], b.shape[1]), a.semiring,
        capacity=capacity, lib=a.lib)
