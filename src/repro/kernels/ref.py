"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` layer).

Each function here defines the *semantics* the kernels must match; kernel
tests sweep shapes/dtypes and ``assert_allclose`` against these.  They are
also the CPU execution path (this container is CPU-only; TPU is the target).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# --------------------------------------------------------------------------
# Semiring matmul
# --------------------------------------------------------------------------


def semiring_matmul_ref(sr, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """C[i,j] = ⊕_k A[i,k] ⊗ B[k,j] for an arbitrary semiring.

    Fast paths: (∨,∧) and (+,×) use the dot unit; (min,+)/(max,+) use a
    row-chunked broadcast so the materialized intermediate stays bounded.
    """
    name = sr.name
    if name == "bool":
        return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                       preferred_element_type=jnp.float32) > 0.5
    if name in ("nat", "real"):
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    # (min,+) / (max,+): chunk rows to bound the (rows, K, N) intermediate
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    chunk = int(max(1, min(m, (1 << 24) // max(1, k * n))))
    reduce_fn = jnp.min if name == "trop" else jnp.max

    if chunk >= m:
        return reduce_fn(a[:, :, None] + b[None, :, :], axis=1)
    npad = (-m) % chunk
    a_p = jnp.pad(a, ((0, npad), (0, 0)), constant_values=sr.zero) if npad else a
    nchunks = (m + npad) // chunk

    def piece(s):
        # slices the padded rows: a clamped slice of ``a`` would re-read
        # earlier rows for the last chunk
        blk = jax.lax.dynamic_slice_in_dim(a_p, s * chunk, chunk, 0)
        return reduce_fn(blk[:, :, None] + b[None, :, :], axis=1)

    out = jax.lax.map(piece, jnp.arange(nchunks))
    return out.reshape(-1, n)[:m]


def segment_reduce_ref(sr, vals: jnp.ndarray, segment_ids: jnp.ndarray,
                       num_segments: int) -> jnp.ndarray:
    """``out[s] = ⊕_{i: ids[i]=s} vals[i]`` with ⊕ from semiring ``sr``.

    The scatter-reduce behind sparse contraction (SpMV destinations).
    Out-of-range ids (the COO padding sentinel) are dropped.  ``vals`` may
    carry trailing payload axes — ``(cap, B)`` rows for batched SpMM — in
    which case each segment row ⊕-combines whole payload slices (the
    scatter window is then a contiguous row, which is what makes the
    batched serving path memory-efficient on every backend).
    """
    from repro.core import semiring as sr_mod
    base = jnp.full((num_segments,) + vals.shape[1:], sr.zero, sr.dtype)
    return sr_mod.scatter_op(sr.name, base.at[segment_ids])(
        vals, mode="drop")


# --------------------------------------------------------------------------
# Flash attention
# --------------------------------------------------------------------------


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, window: int | None = None,
                  chunk: int | None = None,
                  q_offset: int = 0) -> jnp.ndarray:
    """Reference GQA attention.

    q: (B, Tq, Hq, D); k/v: (B, Tk, Hkv, D) with Hq % Hkv == 0.
    ``window``: sliding-window size (StarCoder2-style); ``chunk``: chunked
    attention (Llama-4-style, attends within aligned chunks only).
    ``q_offset``: absolute position of q[0] (decode: Tk - Tq).
    """
    b, tq, hq, d = q.shape
    _, tk, hkv, _ = k.shape
    group = hq // hkv
    kr = jnp.repeat(k, group, axis=2)
    vr = jnp.repeat(v, group, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / np.sqrt(d)
    qpos = jnp.arange(tq)[:, None] + q_offset
    kpos = jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if chunk is not None:
        mask &= (kpos // chunk) == (qpos // chunk)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # fully-masked rows
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vr)


# --------------------------------------------------------------------------
# SSM / linear-recurrence scan
# --------------------------------------------------------------------------


def ssm_scan_ref(a: jnp.ndarray, b: jnp.ndarray,
                 h0: jnp.ndarray | None = None) -> jnp.ndarray:
    """h_t = a_t * h_{t-1} + b_t along axis 1.  a, b: (B, T, D).

    The sequential FG-loop; the kernel implements the FGH-rewritten
    associative-scan GH-form (DESIGN.md §Arch-applicability).
    """
    if h0 is not None:
        b = b.at[:, 0].set(a[:, 0] * h0 + b[:, 0])
        a = a.at[:, 0].set(0.0)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    av, bv = jax.lax.associative_scan(combine, (a, b), axis=1)
    return bv


def ssm_scan_chunked(a: jnp.ndarray, b: jnp.ndarray,
                     chunk: int = 256) -> jnp.ndarray:
    """Blocked GH-form on the XLA path: lax.scan over chunks carrying the
    boundary state, associative scan within each chunk — mirrors the Pallas
    kernel's grid structure.  Cuts the O(T·log T) intermediate traffic of a
    full-length associative scan to O(T·log chunk) (§Perf)."""
    bsz, t, d = a.shape
    chunk = min(chunk, t)
    if t % chunk != 0:
        return ssm_scan_ref(a, b)
    n = t // chunk
    ac = a.reshape(bsz, n, chunk, d).transpose(1, 0, 2, 3)
    bc = b.reshape(bsz, n, chunk, d).transpose(1, 0, 2, 3)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    def step(carry, xs):
        a_i, b_i = xs
        av, bv = jax.lax.associative_scan(combine, (a_i, b_i), axis=1)
        h = bv + av * carry[:, None, :]
        return h[:, -1], h

    h0 = jnp.zeros((bsz, d), a.dtype)
    _, hs = jax.lax.scan(step, h0, (ac, bc))
    return hs.transpose(1, 0, 2, 3).reshape(bsz, t, d)


def ssm_scan_sequential(a: jnp.ndarray, b: jnp.ndarray,
                        h0: jnp.ndarray | None = None) -> jnp.ndarray:
    """The literal per-token loop (the FG-program): oracle for the oracle."""
    bsz, t, d = a.shape
    h = jnp.zeros((bsz, d), a.dtype) if h0 is None else h0

    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    _, hs = jax.lax.scan(step, h, (a.transpose(1, 0, 2), b.transpose(1, 0, 2)))
    return hs.transpose(1, 0, 2)
