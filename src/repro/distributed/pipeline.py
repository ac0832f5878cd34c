"""GPipe-style pipeline parallelism over a mesh axis (optional feature).

For very deep models (llama3's 126 layers) an alternative to pure scan:
split the layer stack into S stages mapped onto a "stage" mesh axis and
stream M microbatches through with `jax.lax.ppermute` handoffs inside a
`shard_map`.  The schedule is the classic fill/steady/drain loop
(S + M - 1 ticks); bubble fraction = (S-1)/(S+M-1).

This module is self-contained (works on any callable stage function) and
is exercised by tests/test_pipeline.py on local devices; the production
launcher can map "stage" onto the pod axis for cross-pod pipelining,
which converts the per-layer FSDP all-gathers into point-to-point
activation handoffs — the standard trade when DCN bandwidth is the
constraint (DESIGN.md §6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipelined_forward(stage_fn, n_stages: int, n_micro: int):
    """Build fn(stage_params, x_micro) -> y running inside shard_map.

    stage_params: leaves with a leading stage axis (sharded on "stage");
    x_micro: (n_micro, micro_batch, ...) microbatched input, replicated.
    Each device executes its stage; activations hop stage→stage+1 via
    ppermute; outputs collect from the last stage.
    """

    def body(params, xs):
        idx = jax.lax.axis_index("stage")
        ticks = n_stages + n_micro - 1
        micro_shape = xs.shape[1:]
        buf = jnp.zeros(micro_shape, xs.dtype)      # current activation
        outs = jnp.zeros((n_micro,) + micro_shape, xs.dtype)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (when available)
            feed = jnp.where(t < n_micro, t, n_micro - 1)
            x_in = jnp.where(idx == 0, xs[feed], buf)
            y = stage_fn(params, x_in)
            # drop garbage during fill for stage>t
            y = jnp.where(idx <= t, y, jnp.zeros_like(y))
            # last stage emits microbatch t-(S-1)
            out_slot = t - (n_stages - 1)
            slot = jnp.clip(out_slot, 0, n_micro - 1)
            emit = (idx == n_stages - 1) & (out_slot >= 0) & \
                (out_slot < n_micro)
            outs = jax.lax.cond(
                emit, lambda o: o.at[slot].set(y), lambda o: o, outs)
            # hand activations to the next stage
            buf = jax.lax.ppermute(
                y, "stage",
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return buf, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)),
            "stage")
        return outs

    return body


def run_pipeline(mesh: Mesh, stage_fn, stage_params, x_micro, *,
                 n_stages: int, n_micro: int):
    """Execute the pipeline on ``mesh`` (must have a "stage" axis)."""
    body = pipelined_forward(stage_fn, n_stages, n_micro)
    param_spec = jax.tree.map(lambda _: P("stage"), stage_params)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(param_spec, P()), out_specs=P(),
                   check_vma=False)
    return fn(stage_params, x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_stages + n_micro - 1)
