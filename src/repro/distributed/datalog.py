"""Graph-axis sharded fixpoints: row-partitioned COO SpMM under shard_map.

The serve/incremental layers (DESIGN.md §3–§5) make the recursive matvec

    x[y]  =  init[y] ⊕ ⊕_z x[z] ⊗ E[z, y]

fast on one device, but the graph dimension ``n`` still had to fit that
device.  This module partitions the problem along a ``("graph",)`` mesh
axis instead (DESIGN.md §6): **destination-row blocks**.  Device ``k`` of
``D`` owns rows ``[k·nb, (k+1)·nb)`` of ``x``/``Δ`` (``nb = ⌈n/D⌉``) and
the edge tuples *landing* in that block — exactly the hash-partitioned
rule evaluation of Scaling-Up In-Memory Datalog (Fan et al.) with the
join key being the destination vertex, mapped onto semiring SpMM.

Two things make the partition *fast*, not merely correct (DESIGN.md §8):

* **Balanced destination blocks.**  ``shard_relation`` relabels vertices
  (snake-deal by in-degree) so every block owns ≈ nnz/D edges; without
  it a power-law hub block sets the shared static capacity and every
  shard pays the worst shard's padding.  The relabeling ``perm`` lives
  on the :class:`ShardedRelation`; inits are permuted in and answers
  permuted back out, so callers never see the internal id space.
* **Δ-sparse frontier exchange.**  Instead of all-gathering the dense
  frontier every iteration, each shard compacts its local Δ nonzeros to
  a static-capacity ``(ids, values)`` buffer and exchanges only those
  (bit-packing bool payload lanes).  Receivers expand *only the edges
  out of live frontier vertices* through a per-shard CSR-by-source
  index — per-iteration exchange bytes *and* compute become frontier-
  proportional.  A ladder of static capacities (small tier, large tier,
  dense fallback) keeps every shape static; when the globally-agreed
  frontier density exceeds the last tier the round falls back to the
  dense all-gather, so semantics never change.  All branch predicates
  are ``pmax``/``psum``-reduced, keeping the SPMD programs in lockstep.

The exchange geometry (sorted-by-source edge copy + unique-source CSR
index + the relabeling) is cached on the :class:`ShardedRelation` and
rebuilt by :meth:`ShardedRelation.apply_delta`, which is what
invalidates it under streaming updates.

Convergence is a ``psum``-reduced emptiness check of the new Δ, so
every device leaves the ``lax.while_loop`` on the same iteration and
the iteration count — and every answer bit — matches the single-device
runners exactly, whichever exchange tier each round took (⊕ is an
idempotent lattice wherever the fixpoint is defined, so re-grouping
contributions is exact, not merely close).

The cold, warm-start (:func:`sharded_resume_fixpoint`, the incremental
§5 repair path), and batched ``(B, n)`` multi-source forms all share one
loop body, mirroring :mod:`repro.sparse.fixpoint`.

Sharded storage is a :class:`ShardedRelation`: per-shard padded COO
stacked on a leading device axis, local destination indices, global
source indices.  Padding follows the §2 discipline — source sentinel
``n_pad`` gathers the ⊗-identity fill, destination sentinel ``nb`` is
dropped by the scatter, padded values are 0̄ — so per-shard nnz may be
ragged under one static capacity and ``apply_delta`` can route new
tuples into padding slots without retracing compiled consumers.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import semiring as sr_mod
from repro.sparse.coo import SparseRelation

#: the mesh axis name every sharded fixpoint runs over
GRAPH_AXIS = "graph"


def mesh_size(mesh) -> int:
    """Device count along the graph axis of ``mesh`` (a Mesh with a
    "graph" axis, or a plain int D for planning/host-side partitioning)."""
    if isinstance(mesh, int):
        if mesh < 1:
            raise ValueError(f"device count must be ≥ 1, got {mesh}")
        return mesh
    if isinstance(mesh, Mesh):
        if GRAPH_AXIS not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no "
                             f"{GRAPH_AXIS!r} axis — build one with "
                             f"launch.mesh.make_graph_mesh")
        return int(mesh.shape[GRAPH_AXIS])
    raise TypeError(f"mesh must be a Mesh or an int device count, "
                    f"got {type(mesh).__name__}")


def _pow2ceil(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def _balance_perm(dst: np.ndarray, n: int, d: int, nb: int) -> np.ndarray:
    """A vertex relabeling ``perm[old] = new`` that snake-deals vertices
    (sorted by in-degree, descending) across the D destination blocks.

    Every block receives ⌈n/D⌉ or ⌊n/D⌋ vertices and — because heavy
    hubs are dealt one per block per round — ≈ nnz/D edges, so the
    shared static capacity is the *mean* shard's nnz instead of the
    worst block's.  On a 1M-vertex power-law graph this cuts per-shard
    padding (and with it every dense round's gather/scatter work) ~2.8×.
    """
    indeg = np.bincount(dst, minlength=n)
    order = np.argsort(-indeg, kind="stable")
    i = np.arange(n)
    rounds, lane = divmod(i, d)
    blk = np.where(rounds % 2 == 0, lane, d - 1 - lane)
    block = np.empty(n, np.int64)
    block[order] = blk
    pos = np.empty(n, np.int64)
    for k in range(d):
        sel = order[blk == k]
        pos[sel] = np.arange(len(sel))
    return (block * nb + pos).astype(np.int32)


def _build_geometry(coords: np.ndarray, values: np.ndarray,
                    nnz: np.ndarray, nb: int, n_pad: int, sr_np):
    """The Δ-exchange receive geometry for one sharded relation: a
    per-shard copy of the edges sorted by global source plus a unique-
    source CSR index over it (host-side, one pass per shard).

    Returns ``(ssrc, sdst, sval, usrc, ustart)``: sorted sources,
    aligned local destinations and values (dead slots keep the padding
    sentinels), the sorted unique sources padded with ``n_pad`` to a
    power-of-two ``ucap``, and the ``(D, ucap+1)`` CSR run starts.  The
    power-of-two ``ucap`` absorbs ragged unique counts and most
    ``apply_delta`` growth without changing any array shape (and so
    without retracing compiled consumers).
    """
    d, cap = values.shape
    ssrc = np.full((d, cap), n_pad, np.int32)
    sdst = np.full((d, cap), nb, np.int32)
    sval = np.full((d, cap), sr_np.zero, sr_np.dtype)
    uniq, starts = [], []
    for k in range(d):
        c = int(nnz[k])
        order = np.argsort(coords[k, :c, 0], kind="stable")
        ssrc[k, :c] = coords[k, :c, 0][order]
        sdst[k, :c] = coords[k, :c, 1][order]
        sval[k, :c] = values[k, :c][order]
        u, st = np.unique(ssrc[k, :c], return_index=True)
        uniq.append(u)
        starts.append((st, c))
    ucap = _pow2ceil(max(1, max((len(u) for u in uniq), default=1)))
    usrc = np.full((d, ucap), n_pad, np.int32)
    ustart = np.zeros((d, ucap + 1), np.int32)
    for k in range(d):
        u, (st, c) = uniq[k], starts[k]
        usrc[k, :len(u)] = u
        ustart[k, :len(u)] = st
        ustart[k, len(u):] = c
    return ssrc, sdst, sval, usrc, ustart


def default_exchange_caps(nb: int, cap: int) -> tuple[tuple[int, int], ...]:
    """The static-capacity ladder for the Δ-sparse exchange: a list of
    ``(frontier_cap, expansion_cap)`` tiers, cheapest first; rounds
    whose (pmax-agreed) frontier exceeds every tier take the dense
    all-gather fallback.  Per-shard frontier caps are fractions of the
    row block ``nb``; expansion caps are fractions of the edge capacity
    ``cap`` — measured on the CI host as the sweet spot between letting
    light rounds stay tiny and not paying worst-case shapes every round
    (DESIGN.md §8)."""
    tiers = []
    for fs, fe in ((32, 16), (4, 2)):
        cs = min(nb, _pow2ceil(max(64, nb // fs)))
        ce = min(cap, _pow2ceil(max(256, cap // fe)))
        if tiers and (cs, ce) == tiers[-1]:
            continue
        tiers.append((cs, ce))
    return tuple(tiers)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedRelation:
    """A binary S-relation partitioned into D destination-row blocks.

    ``coords[(D, cap, 2)]`` holds per-shard tuples as (global source,
    **local** destination); ``values[(D, cap)]`` their semiring values;
    ``nnz[(D,)]`` the ragged live counts.  ``cap`` is one static
    capacity shared by every shard so the type is a pytree whose leaves
    carry a leading device axis ready for ``P("graph")`` in/out specs.

    When built by :func:`shard_relation` the relation also carries the
    Δ-exchange geometry (module docstring): the balance relabeling
    ``perm``/``inv`` (``None`` = identity) and the sorted-by-source
    CSR index ``ssrc``/``sdst``/``sval``/``usrc``/``ustart`` (``None``
    = dense exchange only).  All ride the pytree so compiled fixpoints
    take them as ordinary sharded operands; :meth:`apply_delta`
    rebuilds them, which is what keeps the cache coherent under
    streaming updates.
    """

    coords: jnp.ndarray   # (D, cap, 2) int32 — [:, :, 0] global src,
    #                       [:, :, 1] local dst (block-relative)
    values: jnp.ndarray   # (D, cap) semiring dtype
    nnz: jnp.ndarray      # (D,) int32 live rows per shard
    shape: tuple[int, ...]
    semiring: str
    # -- Δ-exchange geometry (all None when absent) ------------------------
    perm: jnp.ndarray | None = None     # (n,) int32: new padded id of old
    inv: jnp.ndarray | None = None      # (n_pad,) int32: old id of new
    ssrc: jnp.ndarray | None = None     # (D, cap) int32 sorted global src
    sdst: jnp.ndarray | None = None     # (D, cap) int32 aligned local dst
    sval: jnp.ndarray | None = None     # (D, cap) aligned values
    usrc: jnp.ndarray | None = None     # (D, ucap) int32 unique sources
    ustart: jnp.ndarray | None = None   # (D, ucap+1) int32 CSR run starts

    _GEO_FIELDS = ("perm", "inv", "ssrc", "sdst", "sval", "usrc", "ustart")

    # -- pytree ------------------------------------------------------------
    def tree_flatten(self):
        children = (self.coords, self.values, self.nnz) + tuple(
            getattr(self, f) for f in self._GEO_FIELDS)
        return children, (self.shape, self.semiring)

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, semiring = aux
        return cls(*children[:3], shape, semiring, *children[3:])

    # -- basics ------------------------------------------------------------
    @property
    def d(self) -> int:
        """Shard count D (the graph-axis mesh size this was built for)."""
        return int(self.coords.shape[0])

    @property
    def capacity(self) -> int:
        """Per-shard static capacity."""
        return int(self.coords.shape[1])

    @property
    def row_block(self) -> int:
        """Destination rows per shard, ``nb = ⌈n/D⌉``."""
        return -(-self.shape[1] // self.d)

    @property
    def n_pad(self) -> int:
        """Padded global row count ``nb · D`` (≥ shape[1])."""
        return self.row_block * self.d

    @property
    def has_exchange_geometry(self) -> bool:
        return self.ssrc is not None

    @property
    def lib(self) -> str:
        return "np" if isinstance(self.values, np.ndarray) else "jnp"

    def total_nnz(self) -> int:
        return int(np.asarray(self.nnz).sum())

    def __repr__(self) -> str:
        return (f"ShardedRelation({self.semiring}{list(self.shape)}, "
                f"D={self.d}×nnz≤{self.capacity}, "
                f"rows/shard={self.row_block})")

    def _convert(self, fn, nnz_dtype) -> "ShardedRelation":
        geo = {f: None if getattr(self, f) is None else fn(getattr(self, f))
               for f in self._GEO_FIELDS}
        return ShardedRelation(fn(self.coords), fn(self.values),
                               fn(np.asarray(self.nnz, nnz_dtype)
                                  if self.lib == "np" else self.nnz),
                               self.shape, self.semiring, **geo)

    def as_jnp(self) -> "ShardedRelation":
        return self._convert(jnp.asarray, np.int32)

    def as_np(self) -> "ShardedRelation":
        return self._convert(np.asarray, np.int32)

    # -- streaming updates -------------------------------------------------
    def apply_delta(self, coords, values=None) -> "ShardedRelation":
        """⊕-merge a batch of global-coordinate tuple updates, routing
        each row to its owning destination shard (DESIGN.md §5/§6).

        The incremental overlay discipline of
        :meth:`repro.sparse.coo.SparseRelation.apply_delta` carries over
        shard-wise: rows land in padding slots while every shard fits
        (static capacity — and therefore the compiled fixpoint's trace —
        unchanged), appended duplicates are left for the ⊕-combining
        consumers to merge, and overflow re-pads **all** shards by
        doubling until the worst shard's live count fits (one uniform
        capacity keeps the stacked pytree rectangular; amortized-O(1),
        one retrace per doubling — the §5 discipline, shard-wise).

        The Δ-exchange geometry is **invalidated and rebuilt** here (a
        host-side re-sort): its array shapes are tied to the capacity
        and the power-of-two unique-source cap, so in-capacity deltas
        keep every compiled consumer's trace alive.
        """
        sr = sr_mod.get(self.semiring, lib="np")
        coords = np.asarray(coords, np.int64).reshape(-1, 2)
        if values is None:
            values = np.full(len(coords), sr.one, sr.dtype)
        values = np.asarray(values, sr.dtype).reshape(-1)
        assert len(coords) == len(values), (coords.shape, values.shape)
        if np.any(coords < 0) or np.any(coords >= np.asarray(self.shape)):
            raise ValueError("delta coordinates out of range for shape "
                             f"{self.shape}")
        live = values if self.semiring == "bool" else values != sr.zero
        coords, values = coords[live], values[live]
        if len(values) == 0:
            return self
        host = self.as_np()
        nb = self.row_block
        if host.perm is not None:
            coords = host.perm[coords]      # old ids → balanced ids
        owner = coords[:, 1] // nb
        k = host.nnz.astype(np.int64)
        add = np.bincount(owner, minlength=self.d)
        need = k + add
        cap = self.capacity
        if int(need.max()) > cap:
            cap = max(1, cap)
            while cap < int(need.max()):
                cap <<= 1
        new_coords = np.empty((self.d, cap, 2), np.int32)
        new_coords[:, :, 0] = self.n_pad
        new_coords[:, :, 1] = nb
        new_values = np.full((self.d, cap), sr.zero, sr.dtype)
        new_coords[:, :self.capacity] = host.coords
        new_values[:, :self.capacity] = host.values
        for s in range(self.d):
            sel = owner == s
            if not sel.any():
                continue
            lo = int(k[s])
            hi = lo + int(sel.sum())
            new_coords[s, lo:hi, 0] = coords[sel, 0]
            new_coords[s, lo:hi, 1] = coords[sel, 1] - s * nb
            new_values[s, lo:hi] = values[sel]
        nnz = need.astype(np.int32)
        geo = {}
        if self.has_exchange_geometry:
            g = _build_geometry(new_coords, new_values, nnz, nb,
                                self.n_pad, sr)
            geo = dict(zip(("ssrc", "sdst", "sval", "usrc", "ustart"), g))
        out = ShardedRelation(new_coords, new_values, nnz, self.shape,
                              self.semiring, perm=host.perm, inv=host.inv,
                              **geo)
        return out if self.lib == "np" else out.as_jnp()


def shard_relation(rel: SparseRelation, mesh, *,
                   balance: bool = True) -> ShardedRelation:
    """Partition a binary :class:`SparseRelation` into per-device
    destination-row blocks for ``mesh`` (host-side, one pass).

    Shard ``k`` receives every live tuple ``(i, j, w)`` whose (balanced)
    destination lands in ``[k·nb, (k+1)·nb)``, stored as block-local.
    All shards share one capacity (the worst shard's nnz, min 1) so the
    stacked buffers stay rectangular; per-shard nnz is ragged.

    ``balance=True`` (default) relabels vertices first so edge counts —
    and with them padding, dense-round work, and exchange buffers — are
    near-uniform across blocks (:func:`_balance_perm`); the relabeling
    is carried on the result and inverted at every public boundary.
    The Δ-exchange geometry (module docstring) is built here too.
    """
    if rel.arity != 2:
        raise ValueError(f"graph sharding needs a binary relation, got "
                         f"arity {rel.arity}")
    d = mesh_size(mesh)
    host = rel.as_np()
    k = int(host.nnz)
    src = host.coords[:k, 0].astype(np.int64)
    dst = host.coords[:k, 1].astype(np.int64)
    w = host.values[:k]
    n = rel.shape[1]
    nb = -(-n // d)
    n_pad = nb * d
    perm = inv = None
    if balance and d > 1 and k and rel.shape[0] == rel.shape[1]:
        perm = _balance_perm(dst, n, d, nb)
        inv = np.full(n_pad, n, np.int32)
        inv[perm] = np.arange(n, dtype=np.int32)
        src = perm[src].astype(np.int64)
        dst = perm[dst].astype(np.int64)
    owner = dst // nb
    counts = np.bincount(owner, minlength=d)
    cap = max(1, int(counts.max()) if k else 1)
    sr = sr_mod.get(rel.semiring, lib="np")
    coords = np.empty((d, cap, 2), np.int32)
    coords[:, :, 0] = n_pad
    coords[:, :, 1] = nb
    values = np.full((d, cap), sr.zero, sr.dtype)
    order = np.argsort(owner, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)])
    for s in range(d):
        sel = order[starts[s]:starts[s + 1]]
        c = len(sel)
        coords[s, :c, 0] = src[sel]
        coords[s, :c, 1] = dst[sel] - s * nb
        values[s, :c] = w[sel]
    nnz = counts.astype(np.int32)
    ssrc, sdst, sval, usrc, ustart = _build_geometry(
        coords, values, nnz, nb, n_pad, sr)
    out = ShardedRelation(coords, values, nnz, rel.shape, rel.semiring,
                          perm=perm, inv=inv, ssrc=ssrc, sdst=sdst,
                          sval=sval, usrc=usrc, ustart=ustart)
    return out if rel.lib == "np" else out.as_jnp()


def unshard(sh: ShardedRelation, *,
            capacity: int | None = None) -> SparseRelation:
    """Reassemble the global COO relation (host-side, coalescing ⊕ at
    duplicate keys and inverting the balance relabeling — the
    round-trip inverse of :func:`shard_relation`)."""
    host = sh.as_np()
    nb = sh.row_block
    coords, values = [], []
    for s in range(sh.d):
        c = int(host.nnz[s])
        blk = host.coords[s, :c].astype(np.int64)
        src, dst = blk[:, 0], blk[:, 1] + s * nb
        if host.inv is not None:
            src, dst = host.inv[src], host.inv[dst]
        coords.append(np.stack([src, dst], axis=1))
        values.append(host.values[s, :c])
    coords = np.concatenate(coords) if coords else np.zeros((0, 2),
                                                            np.int64)
    values = np.concatenate(values) if values else np.zeros(
        0, sr_mod.get(sh.semiring, lib="np").dtype)
    return SparseRelation.from_coo(coords, values, sh.shape, sh.semiring,
                                   capacity=capacity, lib=sh.lib)


# --------------------------------------------------------------------------
# The sharded GSN loop
# --------------------------------------------------------------------------


def _local_derive(sr, coords, values, d_full, nb: int):
    """One shard's δF: gather the gathered frontier at the global source
    coordinates, ⊗ with the local edge values, ⊕-segment-reduce by local
    destination.  ``d_full`` is (n_pad,) or (n_pad, B); the result is
    (nb,) or (nb, B).  The padding discipline (sentinel src → ⊗-identity
    fill, 0̄ values, OOB dst dropped) makes ragged per-shard nnz exact."""
    from repro.kernels import ops as kops
    gathered = jnp.take(d_full, coords[:, 0], axis=0, mode="fill",
                        fill_value=sr.one)
    if d_full.ndim == 1:
        prod = sr.mul(values, gathered)
    else:
        prod = sr.mul(values[:, None], gathered)
    return kops.semiring_segment_reduce(sr, prod, coords[:, 1], nb)


def _pad_rows(x, n_pad: int, fill):
    """Zero-pad the vertex axis (axis 0) of a (n,)/(n, B) array to
    ``n_pad`` phantom rows (0̄ init, never referenced by any edge)."""
    n = x.shape[0]
    if n == n_pad:
        return x
    pad = jnp.full((n_pad - n,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([x, pad], axis=0)


def _payload_codec(sr, batched: bool):
    """(pack, unpack, bytes-per-row) for the exchanged Δ payload.
    Batched bool lanes bit-pack 8-to-a-byte (exact round trip), cutting
    both the dense-fallback all-gather and the sparse buffers 8×."""
    if batched and sr.dtype == jnp.bool_:
        def pack(x):
            return jnp.packbits(x.astype(jnp.uint8), axis=1)

        def unpack(p, b):
            return jnp.unpackbits(p, axis=1, count=b).astype(jnp.bool_)

        return pack, unpack, None  # bytes/row depends on B: ⌈B/8⌉
    return (lambda x: x), (lambda p, b: p), None


def payload_row_bytes(semiring: str, batch: int) -> int:
    """Exchanged bytes per vertex row of Δ payload (after bit-packing)."""
    sr = sr_mod.get(semiring)
    if batch > 1 and sr.dtype == jnp.bool_:
        return -(-batch // 8)
    return batch * np.dtype(sr.dtype).itemsize


def _sparse_exchange_derive(sr, dense_fn, geo, d_loc, *, nb, n_pad, cap,
                            caps, batched, batch):
    """One Δ-sparse derive round under the capacity ladder.

    Returns ``(derived, tier)`` where ``tier`` indexes ``caps`` (or
    ``len(caps)`` for the dense fallback).  Every branch predicate is
    reduced over the graph axis first, so all shards take the same
    branch (collectives inside `lax.cond` stay matched)."""
    ssrc, sdst, sval, usrc, ustart = geo
    zero = jnp.asarray(sr.zero, sr.dtype)
    pack, unpack, _ = _payload_codec(sr, batched)
    dense_tier = jnp.int32(len(caps))

    if batched:
        live = jnp.any(d_loc != zero, axis=1)
    else:
        live = d_loc != zero
    cnt_max = jax.lax.pmax(jnp.sum(live.astype(jnp.int32)), GRAPH_AXIS)

    def expand(V, stt, deg, offs, total, cap_e):
        """Static-shape CSR expansion of the gathered compact frontier:
        edge slot e belongs to gathered entry `row(e)` (scatter + cummax
        instead of a per-edge searchsorted), expanded edges ⊗ their
        source's Δ value, segment-⊕ by local destination.  Slots past
        the *local* total hit the padding sentinels and vanish."""
        starts_ex = offs - deg
        ridx = jnp.zeros((cap_e,), jnp.int32)
        ridx = ridx.at[jnp.where(deg > 0, starts_ex, cap_e)].max(
            jnp.arange(deg.shape[0], dtype=jnp.int32), mode="drop")
        row = jax.lax.cummax(ridx)
        e = jnp.arange(cap_e, dtype=jnp.int32)
        within = e - jnp.take(starts_ex, row, mode="fill", fill_value=0)
        slot = jnp.take(stt, row, mode="fill", fill_value=0) + within
        slot = jnp.where(e < total, slot, cap)
        dsts = jnp.take(sdst, slot, mode="fill", fill_value=nb)
        ws = jnp.take(sval, slot, mode="fill", fill_value=sr.zero)
        srcv = jnp.take(V, row, axis=0, mode="fill", fill_value=sr.zero)
        prod = sr.mul(ws[:, None], srcv) if batched else sr.mul(ws, srcv)
        from repro.kernels import ops as kops
        return kops.semiring_segment_reduce(sr, prod, dsts, nb)

    def sparse_tier(dl, cap_s, cap_e, tier):
        (idx,) = jnp.nonzero(live, size=cap_s, fill_value=nb)
        idx = idx.astype(jnp.int32)
        vals = jnp.take(dl, idx, axis=0, mode="fill", fill_value=sr.zero)
        me = jax.lax.axis_index(GRAPH_AXIS)
        gsrc = jnp.where(idx == nb, n_pad, me * nb + idx)
        # the id gather is issued first so the CSR lookup below can
        # overlap the (larger) payload transfer on async backends
        G = jax.lax.all_gather(gsrc, GRAPH_AXIS, axis=0, tiled=True)
        V = jax.lax.all_gather(pack(vals), GRAPH_AXIS, axis=0, tiled=True)
        pos = jnp.searchsorted(usrc, G).astype(jnp.int32)
        hit = jnp.take(usrc, pos, mode="fill", fill_value=-1) == G
        stt = jnp.take(ustart, pos, mode="fill", fill_value=0)
        en = jnp.take(ustart, pos + 1, mode="fill", fill_value=0)
        deg = jnp.where(hit, en - stt, 0)
        offs = jnp.cumsum(deg)
        total = offs[-1]
        over = jax.lax.pmax(total, GRAPH_AXIS) > cap_e
        return jax.lax.cond(
            over,
            lambda op: (dense_fn(op[0]), dense_tier),
            lambda op: (expand(unpack(op[1], batch), op[2], op[3], op[4],
                               op[5], cap_e), jnp.int32(tier)),
            (dl, V, stt, deg, offs, total))

    def build(i):
        if i == len(caps):
            return lambda dl: (dense_fn(dl), dense_tier)
        cs, ce = caps[i]
        nxt = build(i + 1)
        return lambda dl: jax.lax.cond(
            cnt_max <= cs,
            lambda q: sparse_tier(q, cs, ce, i),
            nxt, dl)

    return build(0)(d_loc)


def sharded_seminaive_fixpoint(edges, init, *, mesh: Mesh,
                               max_iters: int = 10_000,
                               exchange: str = "auto",
                               exchange_caps=None):
    """Least fixpoint of ``x = init ⊕ x ⊗ E`` with the graph axis
    partitioned across ``mesh`` (module docstring).

    ``edges`` is a :class:`ShardedRelation` built for the mesh's D (or a
    plain :class:`SparseRelation`, sharded here).  ``init`` may be
    ``(n,)`` or a batched ``(B, n)`` multi-source pack; results and
    iteration counts match :func:`repro.sparse.fixpoint.
    sparse_seminaive_fixpoint` exactly, row for row.

    ``exchange`` selects the per-iteration frontier exchange:
    ``"auto"`` (default) runs the Δ-sparse ladder with its dense
    fallback; ``"dense"`` forces the reference all-gather every round.
    Both produce bit-identical answers — "dense" is the oracle the
    property tests hold "auto" to.  ``exchange_caps`` overrides the
    ladder (a tuple of ``(frontier_cap, expansion_cap)`` tiers) — the
    fallback boundary's test hook and the benchmark's tuning knob.
    """
    y, iters, _ = _dispatch(edges, mesh, init=init, max_iters=max_iters,
                            exchange=exchange, exchange_caps=exchange_caps)
    return y, iters


def sharded_seminaive_fixpoint_stats(edges, init, *, mesh: Mesh,
                                     max_iters: int = 10_000,
                                     exchange: str = "auto",
                                     exchange_caps=None):
    """:func:`sharded_seminaive_fixpoint` plus the exchange round
    counters: ``(y, iters, rounds)`` where ``rounds[i]`` counts derive
    rounds taken by ladder tier ``i`` and ``rounds[-1]`` the dense
    fallbacks — the benchmark's exchanged-byte accounting input
    (:func:`exchange_byte_report`)."""
    return _dispatch(edges, mesh, init=init, max_iters=max_iters,
                     exchange=exchange, exchange_caps=exchange_caps)


def sharded_resume_fixpoint(edges, y0, d0, *, mesh: Mesh,
                            max_iters: int = 10_000,
                            exchange: str = "auto",
                            exchange_caps=None):
    """Warm-start re-convergence from a ``(y0, d0)`` pre-fixpoint pair —
    the sharded twin of :func:`repro.sparse.fixpoint.resume_fixpoint`,
    sharing this module's loop body (and its Δ-sparse exchange).  Used
    by the serve loop to repair warm answers after a monotone update
    (DESIGN.md §5/§6)."""
    y, iters, _ = _dispatch(edges, mesh, warm=(y0, d0),
                            max_iters=max_iters, exchange=exchange,
                            exchange_caps=exchange_caps)
    return y, iters


def sharded_resume_chunk(edges, y0, d0, it0, *, mesh: Mesh,
                         max_iters: int, exchange: str = "auto",
                         exchange_caps=None):
    """One bounded slice of the sharded batched GSN loop — the graph-axis
    twin of :func:`repro.sparse.fixpoint.resume_fixpoint_chunk` and the
    ``sparse_sharded`` runner's ``run_chunk`` body (DESIGN.md §10).

    Advances the ``(B, n)`` carry ``(y0, d0)`` by at most ``max_iters``
    rounds (Δ-sparse exchange and all) and returns the full carry
    ``(y, d, it_rows)`` in global vertex coordinates, so the adaptive
    executor can hand it to any single-device runner — the round body is
    shared, so the hand-off is bit-exact.  ``it0`` is the ``(B,)``
    per-row iteration counter carried across chunks.
    """
    if np.ndim(y0) != 2:
        raise ValueError("sharded_resume_chunk needs a batched (B, n) "
                         "carry — add a leading batch axis")
    return _dispatch(edges, mesh, warm=(y0, d0), it0=it0, chunk=True,
                     max_iters=max_iters, exchange=exchange,
                     exchange_caps=exchange_caps)


def exchange_byte_report(es: ShardedRelation, rounds, *, batch: int = 1,
                         exchange_caps=None) -> dict:
    """Exchanged-byte accounting for one fixpoint run: ``rounds`` is the
    counter vector from :func:`sharded_seminaive_fixpoint_stats`.  The
    baseline is what the PR-5 *reference* exchange would have moved —
    one ``n_pad``-row all-gather of the raw (unpacked) payload per
    round; "actual" prices each round at the buffer its tier really
    gathered (ids + bit-packed payload; the dense fallback also packs,
    so even forced-dense rounds undercut the reference on 𝔹 rows)."""
    rounds = np.asarray(rounds, np.int64)
    caps = tuple(exchange_caps or default_exchange_caps(es.row_block,
                                                        es.capacity))
    assert len(rounds) == len(caps) + 1, (rounds, caps)
    prow = payload_row_bytes(es.semiring, batch)
    raw = max(1, batch) * np.dtype(sr_mod.get(es.semiring).dtype).itemsize
    dense_ref = es.n_pad * raw
    per_round = [es.d * cs * (4 + prow) for cs, _ in caps] \
        + [es.n_pad * prow]
    total = int(np.dot(rounds, per_round))
    nrounds = max(1, int(rounds.sum()))
    return {
        "rounds": rounds.tolist(),
        "bytes_per_iter": total / nrounds,
        "dense_bytes_per_iter": float(dense_ref),
        "bytes_total": total,
        "dense_bytes_total": float(dense_ref * nrounds),
        "byte_reduction": (dense_ref * nrounds) / max(1, total),
    }


def sharded_contract(edges, x, *, mesh: Mesh):
    """One sharded ``x ⊗ E`` application: all-gather the operand, derive
    locally, return the row-sharded product reassembled to ``(n,)`` /
    ``(B, n)``.  Defined for *every* semiring (no ⊖ needed) — the
    exact-agreement probe for non-lattice semirings like ℕ∞.  One-shot
    (no iteration), so it keeps the dense exchange: there is no Δ to
    be sparse in."""
    es = _as_sharded(edges, mesh)
    sr = sr_mod.get(es.semiring)
    batched = np.ndim(x) == 2
    n, nb, n_pad = es.shape[1], es.row_block, es.n_pad
    xv = jnp.asarray(x).T if batched else jnp.asarray(x)
    if es.perm is not None:
        xv = _permute_rows(xv, es.perm, n_pad, sr.zero)
    else:
        xv = _pad_rows(xv, n_pad, sr.zero)
    vspec = P(GRAPH_AXIS, None) if batched else P(GRAPH_AXIS)

    def body(coords, values, x_loc):
        full = jax.lax.all_gather(x_loc, GRAPH_AXIS, axis=0, tiled=True)
        return _local_derive(sr, coords[0], values[0], full, nb)

    out = shard_map(body, mesh=mesh,
                    in_specs=(P(GRAPH_AXIS), P(GRAPH_AXIS), vspec),
                    out_specs=vspec, check_vma=False)(
        es.coords, es.values, xv)
    out = jnp.take(out, es.perm, axis=0) if es.perm is not None \
        else out[:n]
    return out.T if batched else out


def _permute_rows(x, perm, n_pad: int, fill):
    """Scatter an (n,)/(n, B) vertex-major array into the balanced id
    space: row ``perm[v]`` of the (n_pad,)-row result holds old row
    ``v``; unassigned padding rows stay 0̄."""
    out = jnp.full((n_pad,) + x.shape[1:], fill, x.dtype)
    return out.at[perm].set(x)


def _as_sharded(edges, mesh) -> ShardedRelation:
    if isinstance(edges, ShardedRelation):
        if edges.d != mesh_size(mesh):
            raise ValueError(
                f"relation sharded for D={edges.d} cannot run on a "
                f"{mesh_size(mesh)}-device graph mesh — re-shard it")
        return edges.as_jnp()
    if isinstance(edges, SparseRelation):
        return shard_relation(edges, mesh).as_jnp()
    raise TypeError(f"edges must be a SparseRelation or ShardedRelation, "
                    f"got {type(edges).__name__}")


def _dispatch(edges, mesh, *, init=None, warm=None, max_iters=10_000,
              exchange="auto", exchange_caps=None, it0=None, chunk=False):
    if exchange not in ("auto", "dense"):
        raise ValueError(f"exchange must be 'auto' or 'dense', "
                         f"got {exchange!r}")
    es = _as_sharded(edges, mesh)
    if es.shape[0] != es.shape[1]:
        raise ValueError(f"recursive expansion needs a square binary "
                         f"edge relation, got shape {es.shape}")
    sr = sr_mod.get(es.semiring)
    if sr.minus is None:
        raise ValueError(f"semiring {sr.name} lacks ⊖; "
                         "GSN needs an idempotent lattice")
    batched = np.ndim(init if warm is None else warm[0]) == 2
    n, nb, n_pad = es.shape[1], es.row_block, es.n_pad
    use_sparse = exchange == "auto" and es.has_exchange_geometry
    caps = tuple(exchange_caps) if exchange_caps else \
        default_exchange_caps(nb, es.capacity)
    n_tiers = len(caps) if use_sparse else 0
    pack, unpack, _ = _payload_codec(sr, batched)

    def seed(x):
        x = jnp.asarray(x)
        x = x.T if batched else x
        if es.perm is not None:
            return _permute_rows(x, es.perm, n_pad, sr.zero)
        return _pad_rows(x, n_pad, sr.zero)

    # vertex-major layout throughout: (n_pad,) or (n_pad, B), sharded on
    # the vertex axis; the (B,) batch axis stays replicated
    vspec = P(GRAPH_AXIS, None) if batched else P(GRAPH_AXIS)
    if warm is None:
        carry_in = (seed(init),)
        wspecs = (vspec,)
    else:
        carry_in = (seed(warm[0]), seed(warm[1]))
        wspecs = (vspec, vspec)
    if chunk:
        # the (B,) iteration counter rides along replicated; the chunk
        # path is batched-warm only (the resumable-carry contract)
        assert warm is not None and batched
        carry_in = carry_in + (jnp.asarray(it0, jnp.int32),)
        wspecs = wspecs + (P(None),)
    geo_in = (es.ssrc, es.sdst, es.sval, es.usrc, es.ustart) \
        if use_sparse else ()

    def changed_of(d_loc):
        """psum-reduced emptiness of the new Δ — the global convergence
        check every device agrees on (batched: per-source (B,) mask)."""
        if batched:
            local = jnp.any(d_loc != sr.zero, axis=0).astype(jnp.int32)
        else:
            local = jnp.any(d_loc != sr.zero).astype(jnp.int32)
        return jax.lax.psum(local, GRAPH_AXIS) > 0

    def body(coords, values, *rest):
        coords, values = coords[0], values[0]
        geo = tuple(g[0] for g in rest[:len(geo_in)])
        carry = rest[len(geo_in):]

        def dense_derive(d_loc):
            full = jax.lax.all_gather(pack(d_loc), GRAPH_AXIS, axis=0,
                                      tiled=True)
            if batched:
                full = unpack(full, d_loc.shape[1])
            return _local_derive(sr, coords, values, full, nb)

        def derive(d_loc, rc):
            if not use_sparse:
                return dense_derive(d_loc), rc.at[n_tiers].add(1)
            out, tier = _sparse_exchange_derive(
                sr, dense_derive, geo, d_loc, nb=nb, n_pad=n_pad,
                cap=es.capacity, caps=caps, batched=batched,
                batch=d_loc.shape[1] if batched else 1)
            return out, rc.at[tier].add(1)

        rc0 = jnp.zeros((n_tiers + 1,), jnp.int32)
        it_start = None
        if warm is None:
            (i_loc,) = carry
            x0 = jnp.full_like(i_loc, sr.zero)
            d0_raw, rc0 = derive(x0, rc0)
            d_loc = sr.minus(sr.add(i_loc, d0_raw), x0)
            # cold start mirrors the single-device runners exactly: the
            # first round always executes (live0 ≡ true), even when the
            # init is already a fixpoint — iteration counts must match
            # bit for bit.  Warm restarts check the seeded Δ instead.
            if batched:
                live0 = jnp.ones((d_loc.shape[1],), bool)
            else:
                live0 = jnp.asarray(True)
        else:
            if chunk:
                x0, d_loc, it_start = carry
            else:
                x0, d_loc = carry
            live0 = changed_of(d_loc)
        if batched:
            b = d_loc.shape[1]
            if it_start is None:
                it_start = jnp.zeros((b,), jnp.int32)

            def cond(c):
                y, d, live, it_rows, it, rc = c
                return jnp.logical_and(jnp.any(live), it < max_iters)

            def step(c):
                y, d, live, it_rows, it, rc = c
                y_new = sr.add(y, d)
                d_raw, rc = derive(d, rc)
                d_new = sr.minus(d_raw, y_new)
                live_new = changed_of(d_new)
                return y_new, d_new, live_new, it_rows + live, it + 1, rc

            y, d, _, it_rows, _, rc = jax.lax.while_loop(
                cond, step, (x0, d_loc, live0, it_start, jnp.asarray(0),
                             rc0))
            # per-source counts are psum-derived, identical on every
            # device — tile to (1, B) so the out spec stays sharded
            if chunk:
                return y, d, it_rows[None, :]
            return y, it_rows[None, :], rc[None, :]

        def cond(c):
            y, d, ch, it, rc = c
            return jnp.logical_and(ch, it < max_iters)

        def step(c):
            y, d, _, it, rc = c
            y_new = sr.add(y, d)
            d_raw, rc = derive(d, rc)
            d_new = sr.minus(d_raw, y_new)
            return y_new, d_new, changed_of(d_new), it + 1, rc

        y, _, _, iters, rc = jax.lax.while_loop(
            cond, step, (x0, d_loc, live0, jnp.asarray(0), rc0))
        return y, jnp.broadcast_to(iters, (1,)), rc[None, :]

    ispec = P(GRAPH_AXIS, None) if batched else P(GRAPH_AXIS)
    out_specs = (vspec, vspec, ispec) if chunk \
        else (vspec, ispec, P(GRAPH_AXIS, None))
    y, second, third = shard_map(
        body, mesh=mesh,
        in_specs=(P(GRAPH_AXIS), P(GRAPH_AXIS))
        + (P(GRAPH_AXIS),) * len(geo_in) + wspecs,
        out_specs=out_specs,
        check_vma=False)(
        es.coords, es.values, *geo_in, *carry_in)
    y = jnp.take(y, es.perm, axis=0) if es.perm is not None else y[:n]
    if chunk:
        d = jnp.take(second, es.perm, axis=0) if es.perm is not None \
            else second[:n]
        return y.T, d.T, third[0]
    iters, rounds = second, third
    if batched:
        return y.T, iters[0], rounds[0]
    return y, iters[0], rounds[0]
