"""Public jit'd wrappers over the Pallas kernels with platform dispatch.

On TPU the Pallas kernels run compiled; on other backends the ``ref.py``
oracles execute.  ``force_pallas_interpret()`` lets tests route a CPU host
through the kernels in interpret mode.  Interpret mode follows from the
backend alone (:func:`pallas_interpret`), so a TPU never takes it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.semiring_matmul import semiring_matmul_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas

_FORCE_INTERPRET = False


def force_pallas_interpret(on: bool = True) -> None:
    """Route ops through the Pallas kernels off-TPU, interpreted (tests)."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = on


def pallas_interpret() -> bool:
    """True iff a Pallas kernel must run interpreted here: off a TPU."""
    return jax.default_backend() != "tpu"


def _use_pallas() -> bool:
    return _FORCE_INTERPRET or not pallas_interpret()


def semiring_matmul(sr, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """C = A ⊕.⊗ B over semiring ``sr`` (2-D a, b)."""
    if _use_pallas():
        return semiring_matmul_pallas(a, b, sr_name=sr.name,
                                      interpret=pallas_interpret())
    return ref.semiring_matmul_ref(sr, a, b)


def semiring_segment_reduce(sr, vals: jnp.ndarray,
                            segment_ids: jnp.ndarray,
                            num_segments: int) -> jnp.ndarray:
    """``out[s] = ⊕ vals[i]`` over ``segment_ids[i] = s`` (sparse scatter).

    ``vals`` may carry trailing payload axes (batched SpMM rows); the
    Pallas kernel currently handles scalar payloads only, so payload
    shapes route through the jnp reference on every platform.
    """
    if _use_pallas() and vals.ndim == 1:
        from repro.kernels.coo_segment import segment_reduce_pallas
        return segment_reduce_pallas(vals, segment_ids, num_segments,
                                     sr_name=sr.name,
                                     interpret=pallas_interpret())
    return ref.segment_reduce_ref(sr, vals, segment_ids, num_segments)


def coo_spmm(rel, x, *, transpose: bool = False):
    """Fused batched COO semiring SpMM with platform dispatch.

    On TPU (or under interpret forcing) the fused Pallas kernel runs;
    elsewhere the host-numpy fused executor does — both via the cached
    geometry of :mod:`repro.kernels.coo_spmm`.  Needs a concrete
    operator; traceable callers use ``sparse.contract.spmm`` directly.
    """
    from repro.kernels import coo_spmm as fused
    plan = fused.plan_geometry(rel, transpose=transpose)
    if _use_pallas():
        return fused.spmm_pallas(plan, x, interpret=pallas_interpret())
    return fused.spmm_host(plan, x)


def flash_attention(q, k, v, *, causal=True, window=None, chunk=None,
                    q_offset=0):
    """GQA flash attention (forward); see ref.attention_ref for semantics."""
    if _use_pallas():
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      chunk=chunk, q_offset=q_offset,
                                      interpret=pallas_interpret())
    return ref.attention_ref(q, k, v, causal=causal, window=window,
                             chunk=chunk, q_offset=q_offset)


#: XLA-path scan lowering: "assoc" (full-length associative scan) or
#: "chunked" (blocked GH-form; §Perf hillclimb)
SCAN_IMPL = "assoc"


def set_scan_impl(impl: str):
    global SCAN_IMPL
    assert impl in ("assoc", "chunked")
    SCAN_IMPL = impl


def ssm_scan(a, b):
    """Diagonal linear recurrence h_t = a_t ⊙ h_{t-1} + b_t over axis 1."""
    if _use_pallas():
        t = a.shape[1]
        bt = 256 if t % 256 == 0 else _largest_pow2_divisor(t)
        return ssm_scan_pallas(a, b, bt=bt, interpret=pallas_interpret())
    if SCAN_IMPL == "chunked":
        return ref.ssm_scan_chunked(a, b)
    return ref.ssm_scan_ref(a, b)


def _largest_pow2_divisor(t: int, cap: int = 256) -> int:
    d = 1
    while t % (d * 2) == 0 and d * 2 <= cap:
        d *= 2
    return d
