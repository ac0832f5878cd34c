"""Blocked semiring matmul as a Pallas TPU kernel.

This is the compute hot-spot of dense Datalog° evaluation (DESIGN.md §2): a
binary-join-and-aggregate rule body is exactly ``C = A ⊕.⊗ B``.  TPU
adaptation of the Datalog hash-join inner loop:

* HBM→VMEM tiling via BlockSpec, (bm, bk) × (bk, bn) tiles, 128-aligned so
  `(∨,∧)`/`(+,×)` hit the MXU (boolean as f32 dot + threshold) and
  `(min,+)`/`(max,+)` vectorize on the 8×128 VPU lanes;
* the K loop is the innermost grid axis; the output tile is revisited and
  accumulated in place (grid iteration on TPU is sequential, so this is the
  canonical accumulate-in-VMEM pattern);
* tropical tiles accumulate one rank-1 ``(min,+)`` update per k — an
  A column against a B row, both plain 2-D broadcasts — since Mosaic has
  no shape cast for the (bm, bk, bn) broadcast form.

Oracle: ``repro.kernels.ref.semiring_matmul_ref`` — tests sweep shapes and
semirings in interpret mode (CPU container; TPU is the compile target).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import semiring as sr_mod

# (bm, bk, bn): Mosaic takes a block whose last two dims are (8, 128)-
# aligned or equal to the whole (padded) array's
_BLOCKS = (128, 128, 128)


def _dot_kernel(a_ref, b_ref, o_ref, *, k_steps: int, mode: str):
    """(+,×) and (∨,∧) tiles — MXU path."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    part = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    o_ref[...] = o_ref[...] + part
    # boolean thresholding happens outside (single pass over the output)
    del mode


def _trop_kernel(a_ref, b_ref, o_ref, *, k_steps: int, mode: str):
    """(min,+) / (max,+) tiles — VPU path, one rank-1 update per k."""
    kk = pl.program_id(2)
    init, comb = ((jnp.inf, jnp.minimum) if mode == "trop"
                  else (-jnp.inf, jnp.maximum))

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, init)

    a = a_ref[...]  # (bm, bk)
    b = b_ref[...]  # (bk, bn)
    part = a[:, 0:1] + b[0:1, :]
    for k in range(1, a.shape[1]):
        part = comb(part, a[:, k:k + 1] + b[k:k + 1, :])
    o_ref[...] = comb(o_ref[...], part)


def _pad_to(x: jnp.ndarray, m0: int, m1: int, fill) -> jnp.ndarray:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)), constant_values=fill)
    return x


@functools.partial(jax.jit, static_argnames=("sr_name", "interpret"))
def semiring_matmul_pallas(a: jnp.ndarray, b: jnp.ndarray, *,
                           sr_name: str, interpret: bool = False) -> jnp.ndarray:
    """C[i,j] = ⊕_k A[i,k] ⊗ B[k,j] via pl.pallas_call."""
    sr = sr_mod.get(sr_name)
    m, k = a.shape
    _, n = b.shape
    dot_path = sr_name in ("bool", "nat", "real")
    bm, bk, bn = _BLOCKS
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    # MXU/VPU want the minor dims 128-aligned; pad up when tiny
    if dot_path:
        a_p = _pad_to(a.astype(jnp.float32), bm, bk, 0.0)
        b_p = _pad_to(b.astype(jnp.float32), bk, bn, 0.0)
        kernel, out_init = _dot_kernel, jnp.float32
    else:
        # pad with ⊗-identity-absorbing values: A rows pad with 0̄ (inf) is
        # wrong for ⊗ (+); pad A with 0̄ on k so padded k never wins the ⊕.
        a_p = _pad_to(a, bm, bk, sr.zero)
        b_p = _pad_to(b, bk, bn, sr.zero)
        kernel, out_init = _trop_kernel, jnp.float32
    mp, kp = a_p.shape
    _, np_ = b_p.shape
    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(kernel, k_steps=grid[2], mode=sr_name),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_init),
        interpret=interpret,
    )(a_p, b_p)
    out = out[:m, :n]
    if sr_name == "bool":
        out = out > 0.5
    return out
