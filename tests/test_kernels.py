"""Pallas kernels vs jnp oracles — shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal deterministic shim (see helpers.py)
    from helpers import given, settings, strategies as st

from repro.core import semiring as sr_mod
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.semiring_matmul import semiring_matmul_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas

SHAPES = [(8, 16, 8), (32, 64, 16), (128, 128, 128), (130, 70, 60)]


@pytest.mark.parametrize("sr_name", ["bool", "trop", "maxplus", "nat",
                                     "real"])
@pytest.mark.parametrize("shape", SHAPES)
def test_semiring_matmul_kernel(sr_name, shape):
    m, k, n = shape
    rng = np.random.default_rng(hash((sr_name, shape)) % 2**31)
    sr = sr_mod.get(sr_name)
    if sr_name == "bool":
        a = rng.random((m, k)) < 0.3
        b = rng.random((k, n)) < 0.3
    else:
        a = rng.integers(0, 5, (m, k)).astype(np.float32)
        b = rng.integers(0, 5, (k, n)).astype(np.float32)
        if sr_name in ("trop", "maxplus"):
            a[rng.random((m, k)) < 0.2] = sr.zero
            b[rng.random((k, n)) < 0.2] = sr.zero
    got = semiring_matmul_pallas(jnp.asarray(a), jnp.asarray(b),
                                 sr_name=sr_name, interpret=True)
    want = ref.semiring_matmul_ref(sr, jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sr_name", ["trop", "maxplus"])
def test_semiring_matmul_ref_chunked_tail(sr_name):
    """The oracle's row-chunked path (m·k·n past 2^24) with a ragged
    last chunk equals a plain numpy (min|max, +) product."""
    m, k, n = 300, 200, 300
    rng = np.random.default_rng(11)
    a = rng.integers(0, 9, (m, k)).astype(np.float32)
    b = rng.integers(0, 9, (k, n)).astype(np.float32)
    red = np.min if sr_name == "trop" else np.max
    want = red(a[:, :, None] + b[None, :, :], axis=1)
    got = ref.semiring_matmul_ref(sr_mod.get(sr_name), jnp.asarray(a),
                                  jnp.asarray(b))
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("tq,tk,hq,hkv,d", [
    (64, 64, 4, 4, 32),     # MHA
    (64, 64, 8, 2, 32),     # GQA
    (128, 128, 4, 1, 64),   # MQA
])
@pytest.mark.parametrize("variant", ["causal", "window", "chunk", "full"])
def test_flash_attention_kernel(tq, tk, hq, hkv, d, variant):
    rng = np.random.default_rng(0)
    q = rng.standard_normal((2, tq, hq, d)).astype(np.float32)
    k = rng.standard_normal((2, tk, hkv, d)).astype(np.float32)
    v = rng.standard_normal((2, tk, hkv, d)).astype(np.float32)
    kw = dict(causal=variant != "full",
              window=32 if variant == "window" else None,
              chunk=32 if variant == "chunk" else None)
    got = flash_attention_pallas(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), bq=32, bkv=32,
                                 interpret=True, **kw)
    want = ref.attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_decode_offset():
    rng = np.random.default_rng(1)
    q = rng.standard_normal((1, 1, 4, 32)).astype(np.float32)
    k = rng.standard_normal((1, 64, 4, 32)).astype(np.float32)
    v = rng.standard_normal((1, 64, 4, 32)).astype(np.float32)
    got = flash_attention_pallas(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), q_offset=63, bq=1, bkv=32,
                                 interpret=True)
    want = ref.attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             q_offset=63)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=15, deadline=None)
@given(b=st.integers(1, 3), t=st.sampled_from([8, 32, 64, 256]),
       d=st.sampled_from([4, 16]), seed=st.integers(0, 100))
def test_ssm_scan_kernel(b, t, d, seed):
    rng = np.random.default_rng(seed)
    a = rng.uniform(0.5, 1.0, (b, t, d)).astype(np.float32)
    x = rng.standard_normal((b, t, d)).astype(np.float32)
    got = ssm_scan_pallas(jnp.asarray(a), jnp.asarray(x),
                          bt=min(32, t), interpret=True)
    want = ref.ssm_scan_ref(jnp.asarray(a), jnp.asarray(x))
    seq = ref.ssm_scan_sequential(jnp.asarray(a), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(want), np.asarray(seq),
                               rtol=2e-4, atol=2e-4)


def test_scan_is_fgh_rewrite_of_sequential_loop():
    """The associative scan (GH-form) equals the token loop (FG-form):
    the DESIGN.md §Arch-applicability claim, checked numerically."""
    rng = np.random.default_rng(3)
    a = rng.uniform(0.0, 1.0, (2, 128, 8)).astype(np.float32)
    x = rng.standard_normal((2, 128, 8)).astype(np.float32)
    fg = ref.ssm_scan_sequential(jnp.asarray(a), jnp.asarray(x))
    gh = ref.ssm_scan_ref(jnp.asarray(a), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(fg), np.asarray(gh), rtol=1e-4,
                               atol=1e-4)


def test_online_attention_matches_sdpa():
    """§Perf 'online' XLA attention ≡ plain SDPA (all mask variants)."""
    import numpy as np
    from repro.models import attention as A
    rng = np.random.default_rng(0)
    b, tq, tk, hq, hkv, hd = 2, 64, 64, 8, 2, 16
    q = jnp.asarray(rng.standard_normal((b, tq, hq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, tk, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, tk, hkv, hd)), jnp.float32)
    qpos, kpos = jnp.arange(tq), jnp.arange(tk)
    for kw in [dict(causal=True, window=None, chunk=None, is_global=False),
               dict(causal=True, window=16, chunk=None, is_global=False),
               dict(causal=True, window=None, chunk=16, is_global=False)]:
        a1 = A._sdpa(q, k, v, qpos, kpos, **kw)
        a2 = A._sdpa_online(q, k, v, qpos, kpos, **kw)
        np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                                   atol=2e-4, rtol=2e-4)


def test_chunked_scan_matches_ref():
    import numpy as np
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.uniform(0.5, 1.0, (2, 512, 8)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 512, 8)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ref.ssm_scan_ref(a, x)),
        np.asarray(ref.ssm_scan_chunked(a, x, chunk=128)),
        atol=2e-4, rtol=2e-4)
