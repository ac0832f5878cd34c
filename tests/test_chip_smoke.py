"""``chip_smoke.py``'s phases on the CPU at a tiny size, its device check,
and the meshes the sharded paths build.

The script refuses to run without a TPU; these tests call its phase
functions directly (the device check lives in ``main()`` only), with the
Pallas kernels in interpret mode, as every CPU run takes them.
"""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.launch import compile_cache
from repro.launch.mesh import (make_datalog_mesh, make_graph_mesh,
                               make_host_mesh)

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_cpu(smoke, monkeypatch, capsys):
    """Without a TPU the run exits non-zero and prints no ``ok`` line."""
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "off")
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert '"platform": "cpu"' in out


def test_kernel_phase(smoke):
    smoke.kernel_phase(seed=0)


@pytest.mark.parametrize("seed", [0, 3])
def test_serve_phase(smoke, seed):
    """Cold answers, one insert and one delete per family, answers
    checked against the numpy references after each; every request
    served, every warm answer repaired."""
    st = smoke.serve_phase(n=1500, m=3, seed=seed, batch=8, checked=4)
    assert st["failed"] == 0
    assert st["served"] == 3 * 2 * 8
    assert st["updates"] == 4
    assert st["warm_hits"] == 2 * 2 * 4
    assert st["answers_dropped"] == 0


def test_serve_phase_catches_a_wrong_answer(smoke, monkeypatch):
    """A reference that disagrees with the server fails the phase."""
    ref = smoke.ref_reach

    def off_by_one(csr, n, a):
        want = ref(csr, n, a).copy()
        want[(a + 1) % n] ^= True
        return want

    monkeypatch.setattr(smoke, "ref_reach", off_by_one)
    with pytest.raises(smoke.SmokeError, match="differs"):
        smoke.serve_phase(n=600, m=3, seed=0, batch=4, checked=2)


def test_references_match_brute_force(smoke):
    """The script's BFS and Dial search against a dense Floyd–Warshall
    closure on a small random graph."""
    rng = np.random.default_rng(5)
    n = 40
    src, dst = rng.integers(0, n, 90), rng.integers(0, n, 90)
    w = rng.integers(1, 5, 90).astype(np.float64)
    d = np.full((n, n), np.inf)
    np.fill_diagonal(d, 0.0)
    for s, t, c in zip(src, dst, w):
        d[s, t] = min(d[s, t], c)
    for k in range(n):
        d = np.minimum(d, d[:, k:k + 1] + d[k:k + 1, :])
    g = smoke._Graph(n, src, dst, w)
    csr = g.csr()
    for a in range(n):
        assert np.array_equal(smoke.ref_reach(csr, n, a), np.isfinite(d[a]))
        assert np.array_equal(smoke.ref_sssp(csr, n, a),
                              d[a].astype(np.float32))


def test_four_chip_phase_needs_four_devices(smoke):
    """On one device the phase cannot build its mesh (the 4-device run
    itself is rehearsed under XLA_FLAGS host devices)."""
    if len(jax.devices()) >= 4:
        pytest.skip("this process has 4 devices")
    with pytest.raises(ValueError, match="graph mesh needs 4 devices"):
        smoke.four_chip_phase(n=200, m=2, queries=2)


@pytest.mark.parametrize("build,axis", [
    (make_host_mesh, "data"),
    (lambda: make_graph_mesh(1), "graph"),
    (lambda: make_datalog_mesh(1), "data"),
], ids=["host", "graph", "datalog"])
def test_meshes_accept_sharding_constraints(build, axis):
    """The meshes carry Auto axes, so the in-jit sharding constraints of
    the serve and sharded paths accept them (jax's make_mesh default,
    Explicit, is refused by with_sharding_constraint)."""
    mesh = build()
    assert all(t == AxisType.Auto for t in mesh.axis_types)
    x = jnp.arange(8 * len(jax.devices()), dtype=jnp.float32)
    out = jax.jit(lambda v: jax.lax.with_sharding_constraint(
        v * 2, NamedSharding(mesh, P(axis))))(x)
    assert np.array_equal(np.asarray(out), np.asarray(x) * 2)


def test_compile_cache_dir(monkeypatch):
    """The environment's directory is honoured and nothing is set;
    otherwise the fixed repo path, the same on every call."""
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        first = compile_cache.use_compile_cache()
        assert first == compile_cache.use_compile_cache() \
            == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
