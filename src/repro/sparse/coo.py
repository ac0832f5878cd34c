"""COO semiring tensors with fixed-capacity padded buffers.

A :class:`SparseRelation` stores an S-relation (paper Sec. 2) as a
coordinate list instead of a dense array: ``coords[(cap, r)]`` holds the
keys of the non-0̄ tuples, ``values[(cap,)]`` their semiring values.  The
buffer capacity is **static** so the type is a jax pytree usable under
``jit``/``pjit``/``lax.while_loop``; the live-tuple count ``nnz`` is a
traced scalar.  Padding rows are self-neutralizing twice over:

* padded coordinates hold the out-of-range sentinel ``shape[axis]``, so
  every scatter with ``mode="drop"`` ignores them;
* padded values hold 0̄, so even a clipped gather contributes the ⊕
  identity.

Host-side constructors (``from_dense`` / ``from_coo``) run in numpy and
coalesce duplicate coordinates with ⊕; on-device consumers therefore never
need data-dependent compaction.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import semiring as sr_mod
from repro.trace import span

Array = jnp.ndarray

#: per-semiring combining scatter for materialization (⊕ at duplicate keys)
_NP_COMBINE = sr_mod.NP_COMBINE


def _is_np(x) -> bool:
    return isinstance(x, np.ndarray)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SparseRelation:
    """A semiring S-relation in padded COO form.

    ``coords``/``values``/``nnz`` are array leaves (np or jnp); ``shape``
    and ``semiring`` are static aux data.
    """

    coords: Array  # (capacity, arity) int32
    values: Array  # (capacity,) semiring dtype
    nnz: Array     # () int32 — number of live (non-padding) rows
    shape: tuple[int, ...]
    semiring: str

    # -- pytree ------------------------------------------------------------
    def tree_flatten(self):
        return (self.coords, self.values, self.nnz), (self.shape,
                                                      self.semiring)

    @classmethod
    def tree_unflatten(cls, aux, children):
        coords, values, nnz = children
        shape, semiring = aux
        return cls(coords, values, nnz, shape, semiring)

    # -- basics ------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.coords.shape[0])

    @property
    def arity(self) -> int:
        return int(self.coords.shape[1])

    @property
    def lib(self) -> str:
        return "np" if _is_np(self.values) else "jnp"

    def sr(self) -> sr_mod.Semiring:
        return sr_mod.get(self.semiring, lib=self.lib)

    def density(self) -> float:
        """Live fraction of the dense key space (host-side)."""
        total = float(np.prod(self.shape)) or 1.0
        return float(np.asarray(self.nnz)) / total

    def __repr__(self) -> str:
        return (f"SparseRelation({self.semiring}{list(self.shape)}, "
                f"nnz≤{self.capacity}, lib={self.lib})")

    # -- conversions -------------------------------------------------------
    def to_dense(self):
        """Materialize as a dense S-relation (⊕-combining duplicates)."""
        sr = self.sr()
        if self.lib == "np":
            out = np.full(self.shape, sr.zero, sr.dtype)
            k = int(self.nnz)
            idx = tuple(np.asarray(self.coords[:k]).T)
            _NP_COMBINE[self.semiring].at(out, idx, np.asarray(
                self.values[:k]))
            return out
        base = jnp.full(self.shape, sr.zero, sr.dtype)
        idx = tuple(self.coords.T)
        return sr_mod.scatter_op(self.semiring, base.at[idx])(
            self.values, mode="drop")

    def as_jnp(self) -> "SparseRelation":
        return SparseRelation(jnp.asarray(self.coords),
                              jnp.asarray(self.values),
                              jnp.asarray(self.nnz, jnp.int32),
                              self.shape, self.semiring)

    def as_np(self) -> "SparseRelation":
        return SparseRelation(np.asarray(self.coords),
                              np.asarray(self.values),
                              np.asarray(self.nnz, np.int32),
                              self.shape, self.semiring)

    def transpose(self, axes: tuple[int, ...] | None = None
                  ) -> "SparseRelation":
        axes = axes or tuple(reversed(range(self.arity)))
        xp = np if self.lib == "np" else jnp
        coords = xp.stack([self.coords[:, a] for a in axes], axis=1)
        shape = tuple(self.shape[a] for a in axes)
        return SparseRelation(coords, self.values, self.nnz, shape,
                              self.semiring)

    # -- constructors ------------------------------------------------------
    @classmethod
    def from_coo(cls, coords, values, shape, semiring: str, *,
                 capacity: int | None = None,
                 lib: str = "jnp") -> "SparseRelation":
        """Build from host coordinate/value arrays (coalesces duplicates,
        drops explicit 0̄ entries, pads to ``capacity``)."""
        sr = sr_mod.get(semiring, lib="np")
        coords = np.asarray(coords, np.int64).reshape(-1, len(shape))
        values = np.asarray(values, sr.dtype).reshape(-1)
        assert len(coords) == len(values), (coords.shape, values.shape)
        # coalesce: ⊕-combine duplicate keys
        if len(coords):
            with span("ingest.coalesce"):
                uniq, inv = np.unique(coords, axis=0, return_inverse=True)
                if len(uniq) != len(coords):
                    merged = np.full(len(uniq), sr.zero, sr.dtype)
                    _NP_COMBINE[semiring].at(merged, inv.reshape(-1),
                                             values)
                    coords, values = uniq, merged
        # drop explicit zeros (0̄ tuples are absent by definition)
        if len(values):
            live = values != sr.zero if semiring != "bool" else values
            coords, values = coords[live], values[live]
        nnz = len(values)
        cap = capacity if capacity is not None else max(1, nnz)
        if nnz > cap:
            raise ValueError(f"nnz {nnz} exceeds capacity {cap}")
        pad = cap - nnz
        if pad:
            sentinel = np.tile(np.asarray(shape, np.int64), (pad, 1))
            coords = np.concatenate([coords, sentinel])
            values = np.concatenate(
                [values, np.full(pad, sr.zero, sr.dtype)])
        out = cls(coords.astype(np.int32), values,
                  np.asarray(nnz, np.int32), tuple(shape), semiring)
        return out if lib == "np" else out.as_jnp()

    @classmethod
    def from_dense(cls, arr, semiring: str, *,
                   capacity: int | None = None,
                   lib: str | None = None) -> "SparseRelation":
        lib = lib or ("np" if _is_np(arr) else "jnp")
        sr = sr_mod.get(semiring, lib="np")
        host = np.asarray(arr)
        coords = np.argwhere(host if semiring == "bool"
                             else host != sr.zero)
        values = host[tuple(coords.T)]
        return cls.from_coo(coords, values, host.shape, semiring,
                            capacity=capacity, lib=lib)

    # -- streaming updates -------------------------------------------------
    def apply_delta(self, coords, values=None) -> "SparseRelation":
        """⊕-merge a batch of tuple updates (host-side, O(nnz(Δ))).

        Appends the delta rows into the padding slots when they fit
        (capacity, and therefore every staged consumer's trace, is
        unchanged — the compile caches keep hitting); beyond capacity the
        buffers are re-padded at the next power-of-two capacity ≥ the new
        live count (amortized-O(1) doubling, one retrace per doubling).

        Appended duplicates of live keys are *not* coalesced: every
        consumer (``to_dense`` scatter, segment-reduce contraction) is
        ⊕-combining, and ⊗ distributes over ⊕, so an appended row is
        exactly the ⊕-merge ``E′ = E ⊕ Δ``.  For trop/minplus that makes
        a weight decrease a plain append; a weight *increase* cannot be
        expressed this way (⊕ = min absorbs it) — that is the
        non-monotone case callers must route to a rebuild.

        ``values=None`` fills 1̄ per tuple (bool edge insertions).
        """
        sr = sr_mod.get(self.semiring, lib="np")
        coords = np.asarray(coords, np.int64).reshape(-1, self.arity)
        if values is None:
            values = np.full(len(coords), sr.one, sr.dtype)
        values = np.asarray(values, sr.dtype).reshape(-1)
        assert len(coords) == len(values), (coords.shape, values.shape)
        if np.any(coords < 0) or np.any(coords >= np.asarray(self.shape)):
            raise ValueError("delta coordinates out of range for shape "
                             f"{self.shape}")
        # explicit 0̄ rows are ⊕-identities — drop them up front
        live = values if self.semiring == "bool" else values != sr.zero
        coords, values = coords[live], values[live]
        host = self.as_np()
        k, d = int(host.nnz), len(values)
        if d == 0:
            return self
        need = k + d
        if need <= self.capacity:
            new_coords = host.coords.copy()
            new_values = host.values.copy()
            new_coords[k:need] = coords
            new_values[k:need] = values
            out = SparseRelation(new_coords, new_values,
                                 np.asarray(need, np.int32), self.shape,
                                 self.semiring)
        else:
            # doubling re-pad: a plain prefix-preserving copy, *not* a
            # from_coo re-coalesce — appended duplicates are ⊕-merged by
            # every consumer, and an O(nnz log nnz) re-sort here would
            # make a one-edge update cost as much as a rebuild
            cap = max(1, self.capacity)
            while cap < need:
                cap <<= 1
            pad = cap - need
            sentinel = np.tile(np.asarray(self.shape, np.int64), (pad, 1))
            new_coords = np.concatenate(
                [host.coords[:k], coords, sentinel]).astype(np.int32)
            new_values = np.concatenate(
                [host.values[:k], values,
                 np.full(pad, sr.zero, sr.dtype)])
            out = SparseRelation(new_coords, new_values,
                                 np.asarray(need, np.int32), self.shape,
                                 self.semiring)
        out = out if self.lib == "np" else out.as_jnp()
        if self.arity == 2:
            # extend any cached host CSR adjacency with an O(nnz(Δ))
            # overlay so warm frontier solves never re-sort (DESIGN.md §5)
            from repro.sparse import fixpoint as fx
            fx.register_delta(self, out, coords, values)
        return out

    def _flat_keys(self, coords) -> np.ndarray:
        """Row-major flattened int64 key per coordinate tuple."""
        coords = np.asarray(coords, np.int64).reshape(-1, self.arity)
        return np.ravel_multi_index(tuple(coords.T), self.shape,
                                    mode="clip")

    def delete_keys(self, coords) -> "SparseRelation":
        """Remove the given keys entirely (host-side, O(nnz) vectorized
        mask + stable compaction at the same capacity — no re-sort, no
        re-coalesce).  Deletion is *not* a ⊕-merge — it is the
        non-monotone mutation; callers owning warm fixpoint state must
        repair it via a synthesized maintenance rule or recompute from
        scratch (see :mod:`repro.incremental.maintenance`, DESIGN.md §11).

        Every live copy of a deleted key is removed, including
        un-coalesced duplicates appended by :meth:`apply_delta`.
        """
        coords = np.asarray(coords, np.int64).reshape(-1, self.arity)
        host = self.as_np()
        k = int(host.nnz)
        if k == 0 or len(coords) == 0:
            return self
        gone = self._flat_keys(coords)
        keep = ~np.isin(self._flat_keys(host.coords[:k]), gone)
        kept = int(keep.sum())
        if kept == k:
            return self
        pad = self.capacity - kept
        sentinel = np.tile(np.asarray(self.shape, np.int64), (pad, 1))
        sr = sr_mod.get(self.semiring, lib="np")
        new_coords = np.concatenate(
            [host.coords[:k][keep], sentinel]).astype(np.int32)
        new_values = np.concatenate(
            [host.values[:k][keep], np.full(pad, sr.zero, sr.dtype)])
        out = SparseRelation(new_coords, new_values,
                             np.asarray(kept, np.int32), self.shape,
                             self.semiring)
        out = out if self.lib == "np" else out.as_jnp()
        if self.arity == 2:
            # hand the child a 0̄-poisoned copy of any cached CSR index so
            # warm frontier/maintenance solves never re-sort (DESIGN.md §11)
            from repro.sparse import fixpoint as fx
            fx.register_delete(self, out, coords)
        return out

    def union(self, other: "SparseRelation", *,
              capacity: int | None = None) -> "SparseRelation":
        """⊕-merge two sparse relations (host-side, coalescing)."""
        assert self.shape == other.shape and self.semiring == other.semiring
        a, b = self.as_np(), other.as_np()
        ka, kb = int(a.nnz), int(b.nnz)
        return SparseRelation.from_coo(
            np.concatenate([a.coords[:ka], b.coords[:kb]]),
            np.concatenate([a.values[:ka], b.values[:kb]]),
            self.shape, self.semiring, capacity=capacity, lib=self.lib)
