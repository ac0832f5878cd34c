"""Semiring segment-reduce over COO coordinates as a Pallas TPU kernel.

This is the scatter half of sparse S-relation contraction (DESIGN.md §2):
after the XLA-side gather/⊗, each edge carries a value and a destination
key, and the kernel ⊕-reduces values by key — ``out[s] = ⊕ vals[i]`` over
``ids[i] = s``.  TPUs have no efficient scatter, so the kernel recasts the
reduction as a *block-aligned segment sweep*:

1. (XLA prep, static shapes) keys are bucketed into output blocks of
   ``bn`` lanes; edges are stably sorted by block and packed into
   fixed-capacity chunk rows of ``bk`` edges such that no chunk straddles
   an output block (padding slots carry 0̄, the capacity bound
   ``m//bk + nblocks + 1`` is static);
2. a scalar-prefetched chunk→block map drives the output BlockSpec, the
   canonical Pallas sparse pattern: grid iteration is sequential, each
   output tile is revisited by exactly the chunks of its block and
   accumulated in VMEM;
3. inside a chunk the reduction is a (bk, bn) one-hot compare +
   axis-reduce on the VPU (bk·bn·4 B ≤ 128 KiB of VMEM for 256×128).

Oracle: ``repro.kernels.ref.segment_reduce_ref`` (jnp scatter); tests
sweep semirings/sizes in interpret mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INIT = {"bool": 0.0, "nat": 0.0, "real": 0.0,
         "trop": float("inf"), "maxplus": float("-inf")}


def _kernel(blk_ref, first_ref, vals_ref, loc_ref, o_ref, *, mode: str,
            bk: int, bn: int):
    c = pl.program_id(0)
    init = _INIT[mode]
    if mode in ("bool", "maxplus"):
        red, comb = jnp.max, jnp.maximum
    elif mode == "trop":
        red, comb = jnp.min, jnp.minimum
    else:
        red, comb = jnp.sum, jnp.add

    @pl.when(first_ref[c] == 1)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, init)

    loc = loc_ref[0, :]                                   # (bk,) int32
    vals = vals_ref[0, :]                                 # (bk,) f32
    lanes = jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 1)
    onehot = loc[:, None] == lanes                        # (bk, bn)
    masked = jnp.where(onehot, vals[:, None], init)
    o_ref[...] = comb(o_ref[...], red(masked, axis=0, keepdims=True))


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "sr_name", "bk", "bn",
                                    "interpret"))
def segment_reduce_pallas(vals: jnp.ndarray, segment_ids: jnp.ndarray,
                          num_segments: int, *, sr_name: str,
                          bk: int = 256, bn: int = 128,
                          interpret: bool = False) -> jnp.ndarray:
    """⊕-reduce ``vals`` by ``segment_ids`` into ``num_segments`` slots.

    Out-of-range ids (COO padding) contribute nothing.  Compute runs in
    f32; boolean inputs are thresholded back on exit.
    """
    n = num_segments
    m = int(vals.shape[0])
    is_bool = sr_name == "bool"
    zero = jnp.float32(_INIT[sr_name])
    v = vals.astype(jnp.float32)
    ids = segment_ids.astype(jnp.int32)

    nblocks = -(-n // bn)
    cap_chunks = m // bk + nblocks + 1
    cap_e = cap_chunks * bk

    valid = (ids >= 0) & (ids < n)
    ids_c = jnp.where(valid, ids, 0)
    v = jnp.where(valid, v, zero)
    blk = ids_c // bn
    loc = ids_c % bn

    order = jnp.argsort(blk, stable=True)
    blk_s, loc_s, v_s = blk[order], loc[order], v[order]
    cnt = jnp.zeros((nblocks,), jnp.int32).at[blk].add(1)
    chunks = jnp.maximum(1, -(-cnt // bk))                 # ≥1 per block
    chunk_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(chunks)[:-1]])
    total_chunks = chunk_start[-1] + chunks[-1]

    # chunk c → owning block; the (monotone) tail of unused capacity maps
    # to the last block with first=0 so it only combines 0̄
    cs = jnp.arange(cap_chunks, dtype=jnp.int32)
    owner = jnp.clip(
        jnp.searchsorted(chunk_start, cs, side="right") - 1, 0, nblocks - 1)
    in_use = cs < total_chunks
    blk_of_chunk = jnp.where(in_use, owner, nblocks - 1).astype(jnp.int32)
    first = (in_use & (cs == chunk_start[owner])).astype(jnp.int32)

    # pack sorted edges into their block's chunk rows
    edge_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(cnt)[:-1]])
    pos = jnp.arange(m, dtype=jnp.int32) - edge_start[blk_s]
    slot = chunk_start[blk_s] * bk + pos
    buf_v = jnp.full((cap_e,), zero, jnp.float32).at[slot].set(
        v_s, mode="drop")
    buf_l = jnp.zeros((cap_e,), jnp.int32).at[slot].set(loc_s, mode="drop")

    # one (1, bk) chunk row / (1, bn) output row per grid step: the
    # unit middle axis makes the last two block dims equal the array's,
    # as Mosaic requires of blocks that are not (8, 128)-aligned
    row = pl.BlockSpec((None, 1, bk), lambda c, blk_r, first_r: (c, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(cap_chunks,),
        in_specs=[row, row],
        out_specs=pl.BlockSpec((None, 1, bn),
                               lambda c, blk_r, first_r: (blk_r[c], 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, mode=sr_name, bk=bk, bn=bn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nblocks, 1, bn), jnp.float32),
        interpret=interpret,
    )(blk_of_chunk, first, buf_v.reshape(cap_chunks, 1, bk),
      buf_l.reshape(cap_chunks, 1, bk))
    flat = out.reshape(-1)[:n]
    return flat > 0.5 if is_bool else flat
