"""Compile-only checks of the Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler installed with jax compiles for a
``v5e:2x2`` topology that is described, not attached.  Mosaic refuses
here what interpret mode accepts — blocks off the (8, 128) tiling, shape
casts it has no rule for — so these tests guard the three kernels the
serve path dispatches to on TPU, at the shapes ``chip_smoke.py`` runs
them (and the segment-⊕ at the 1M-vertex serving size).  Each asserts
that the compiled program holds the Mosaic kernel (``tpu_custom_call``).
The 𝔹 serve chunk, which is plain XLA, is compiled here too, and checked
for the scatter and sort it no longer needs.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and the suite's
workers all import this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.datalog import datasets
from repro.kernels import coo_spmm
from repro.kernels.coo_segment import segment_reduce_pallas
from repro.kernels.semiring_matmul import semiring_matmul_pallas
from repro.sparse.coo import SparseRelation

SEMIRINGS = ("bool", "nat", "trop", "maxplus")
DTYPE = {"bool": jnp.bool_, "nat": jnp.float32, "trop": jnp.float32,
         "maxplus": jnp.float32}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent cache would store executables it cannot read back
    # without a chip; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args, **kw):
    compiled = fn.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("sr_name", SEMIRINGS)
def test_coo_spmm_compiles(one_chip, sr_name):
    """The fused SpMM at the geometry of chip_smoke's kernel check
    (600-vertex graph, B=8 lanes padded to 128)."""
    g = datasets.erdos_renyi_sparse(600, 4.0, seed=0)
    vals = (np.ones(len(g.edges), bool) if sr_name == "bool"
            else np.ones(len(g.edges), np.float32))
    rel = SparseRelation.from_coo(g.edges, vals, (g.n, g.n), sr_name)
    plan = coo_spmm.plan_geometry(rel, transpose=True)
    sblk, dblk, first, locs, locd, vbuf, nsb, ndb = \
        coo_spmm._chunk_geometry(plan)
    args = [_shape(one_chip, a.shape, a.dtype)
            for a in (sblk, dblk, first, locs, locd, vbuf)]
    args.append(_shape(one_chip, (nsb * plan.bs, 128), jnp.float32))
    _assert_kernel(coo_spmm._spmm_pallas_call, *args, sr_name=sr_name,
                   bk=plan.bk, bs=plan.bs, bn=plan.bn, ndb=ndb,
                   interpret=False)


@pytest.mark.parametrize("sr_name,m,n", [
    *((s, 5000, 700) for s in SEMIRINGS),
    # one lattice at the 1M-vertex serving size (~30 s: the compile
    # grows with the size; the block layout is the same for all four)
    ("bool", 8_000_000, 1_000_000),
], ids=[*(f"smoke-{s}" for s in SEMIRINGS), "serve1M-bool"])
def test_coo_segment_compiles(one_chip, sr_name, m, n):
    _assert_kernel(
        jax.jit(lambda v, i: segment_reduce_pallas(v, i, n,
                                                   sr_name=sr_name)),
        _shape(one_chip, (m,), DTYPE[sr_name]),
        _shape(one_chip, (m,), jnp.int32))


@pytest.mark.parametrize("sr_name", SEMIRINGS)
@pytest.mark.parametrize("m,k,n", [(300, 200, 300), (5, 3, 7)],
                         ids=["smoke", "tiny"])
def test_semiring_matmul_compiles(one_chip, sr_name, m, k, n):
    """The dense engine's contraction: chip_smoke's shape (tiles padded
    on every axis) and a whole-array tile below the (8, 128) tiling,
    the shape the CEGIS verifier's small probe databases produce."""
    _assert_kernel(
        jax.jit(lambda a, b: semiring_matmul_pallas(a, b,
                                                    sr_name=sr_name)),
        _shape(one_chip, (m, k), DTYPE[sr_name]),
        _shape(one_chip, (k, n), DTYPE[sr_name]))


@pytest.mark.parametrize("b", [64, 33])
def test_packed_chunk_compiles(one_chip, b):
    """The serve pools' 𝔹 chunk (packed pull round) at the cell's lane
    widths on a 2^16-vertex, 2^21-slot operator: its program holds a
    gather and neither a scatter nor a sort."""
    from repro.sparse import fixpoint as fx
    n, cap = 1 << 16, 1 << 21
    e = SparseRelation(_shape(one_chip, (cap, 2), jnp.int32),
                       _shape(one_chip, (cap,), jnp.bool_),
                       _shape(one_chip, (), jnp.int32), (n, n), "bool")
    view = fx.PullView(_shape(one_chip, (cap,), jnp.int32),
                       _shape(one_chip, (cap,), jnp.int32),
                       _shape(one_chip, (n,), jnp.int32), 17)
    carry = (_shape(one_chip, (b, n), jnp.bool_),
             _shape(one_chip, (b, n), jnp.bool_),
             _shape(one_chip, (b,), jnp.int32))
    text = fx.CompiledChunk(4)._jit.lower(e, view, *carry).compile() \
        .as_text()
    assert "gather(" in text
    assert "scatter(" not in text and " sort(" not in text
