"""Distributed-optimization tricks: compressed gradient reduction.

Under pjit, data-parallel gradient reduction is implicit (XLA inserts the
all-reduce).  These helpers implement the *compressed* variants as
shard_map collectives for bandwidth-bound interconnects (DCN between
pods):

* ``bf16_all_reduce`` — cast f32 grads to bf16 for the wire, accumulate
  back in f32 (2× DCN volume reduction, standard at pod boundaries);
* ``int8_all_reduce`` — per-tensor scale + int8 quantization with error
  feedback residual carried by the caller (4×);
* both are exposed through ``compressed_grad_reduce`` which reduces over
  an explicit mesh axis inside shard_map — the training driver uses it
  for the "pod" axis while leaving the intra-pod reduction to XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def bf16_all_reduce(x, axis_name: str):
    return jax.lax.psum(x.astype(jnp.bfloat16), axis_name).astype(x.dtype)


def int8_all_reduce(x, axis_name: str):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    # sum int8 payloads in int32, then rescale; scales are psum-averaged
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    s = jax.lax.psum(scale, axis_name) / jax.lax.psum(1, axis_name)
    return (total.astype(jnp.float32) * s).astype(x.dtype)


def compressed_grad_reduce(grads, mesh, axis_name: str = "pod",
                           mode: str = "bf16"):
    """Reduce a grad pytree over ``axis_name`` with wire compression."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    red = bf16_all_reduce if mode == "bf16" else int8_all_reduce

    def body(g):
        return jax.tree.map(lambda t: red(t, axis_name) /
                            jax.lax.psum(1, axis_name), g)

    spec = jax.tree.map(lambda _: P(), grads)
    return shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                     check_vma=False)(grads)
