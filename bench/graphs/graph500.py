"""The Graph500 Kronecker generator (Graph500 specification, section 3).

Each of ``edgefactor * 2**scale`` edges picks one quadrant of the
adjacency matrix per bit of the vertex id, with probabilities A, B, C
and D = 1 - A - B - C; the vertex labels are then permuted at random.
The edge list keeps its duplicates and self-loops, as the specification
hands them to the kernels.  Weights, where the configuration asks for
them, are uniform in [0, 1) per generated edge (Graph500 SSSP kernel).

The graph itself comes from the configuration's ``graph_seed``, so that
every run serves the same work; ``--seed`` renames its vertices by a
random permutation.  Graph500 runs many searches on one generated graph
in the same way.  Everything is drawn on the default device in one
jitted call: threefry is bit-exact across backends, so a seed gives the
same graph on the CPU and on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _key(seed: int):
    # the seed may exceed 32 bits: fold the high word into the key
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit,
                   static_argnames=("scale", "edgefactor", "weighted"))
def _kronecker(key, names, a, b, c, *, scale: int, edgefactor: int,
               weighted: bool):
    n = 1 << scale
    m = edgefactor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    k_bits, k_perm, k_w = jax.random.split(key, 3)

    def level(ib, ij):
        i, j = ij
        k1, k2 = jax.random.split(jax.random.fold_in(k_bits, ib))
        ii = jax.random.uniform(k1, (m,)) > ab
        jj = jax.random.uniform(k2, (m,)) > jnp.where(ii, c_norm, a_norm)
        return (i + (ii.astype(jnp.int32) << ib),
                j + (jj.astype(jnp.int32) << ib))

    zero = jnp.zeros((m,), jnp.int32)
    i, j = jax.lax.fori_loop(0, scale, level, (zero, zero))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    i, j = perm[i], perm[j]
    # Graph500's search keys: vertices with an edge other than a self-loop
    ends = jnp.where(i != j, 1, 0).astype(jnp.int32)
    linked = (jnp.zeros((n,), jnp.int32).at[i].add(ends)
              .at[j].add(ends)) > 0
    label = jax.random.permutation(names, n).astype(jnp.int32)
    edges = jnp.stack([label[i], label[j]], axis=1)
    w = (jax.random.uniform(k_w, (m,), jnp.float32) if weighted
         else jnp.zeros((0,), jnp.float32))
    return edges, w, label, linked


def generate(params: dict, seed: int):
    """``(n, edges (m, 2) int32, weights (m,) float32 or None, keys)``.

    ``keys`` are the search keys under this seed's names, in an order
    that does not depend on the seed: ``keys[k]`` is the same vertex of
    the graph in every run."""
    scale, ef = int(params["scale"]), int(params["edgefactor"])
    weighted = params.get("weights") == "uniform01"
    edges, w, label, linked = _kronecker(
        _key(int(params["graph_seed"])), jax.random.fold_in(_key(seed), 1),
        float(params["A"]),
        float(params["B"]), float(params["C"]), scale=scale, edgefactor=ef,
        weighted=weighted)
    keys = np.asarray(label)[np.flatnonzero(np.asarray(linked))]
    return 1 << scale, np.asarray(edges), (np.asarray(w) if weighted
                                           else None), keys
