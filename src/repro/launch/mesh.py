"""Production mesh construction (assignment MULTI-POD DRY-RUN §1).

A function, not a module-level constant, so importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, **kw):
    # Auto axes: the sharding rules place arrays with
    # with_sharding_constraint, which refuses Explicit axes (the
    # jax.make_mesh default in the pinned jax)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over the real local devices (tests / examples)."""
    n = len(jax.devices())
    return _mesh((n // model, model), ("data", "model"))


def make_graph_mesh(d: int | None = None):
    """1-D ``("graph",)`` mesh for vertex-partitioned Datalog fixpoints
    (DESIGN.md §6).

    Each of the ``d`` devices (default: all local devices) owns an
    ``n/d`` destination-row block of the fixpoint state and the COO
    edge tuples landing there (:mod:`repro.distributed.datalog`).  On a
    CPU host, simulate devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    n = len(jax.devices())
    d = n if d is None else d
    if d > n:
        raise ValueError(f"graph mesh needs {d} devices, have {n} "
                         f"(set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={d})")
    return _mesh((d,), ("graph",), devices=jax.devices()[:d])


def make_datalog_mesh(data: int | None = None):
    """1-D data mesh for batched query serving (DESIGN.md §3).

    The serve loop shards only the query-batch axis, so the mesh is a
    flat "data" axis over the local devices (or the first ``data`` of
    them); the graph stays replicated.
    """
    n = data if data is not None else len(jax.devices())
    return _mesh((n,), ("data",))
