"""Graph500's Kronecker graph, made on the device in one jitted call.

Follows the specification's reference generator (graph500.org,
``kronecker_generator``): ``edgefactor * 2^scale`` edges, each of whose
``scale`` bit pairs is drawn from the initiator ``[[A, B], [C, D]]``
(``D = 1 - A - B - C``); vertex labels are then permuted at random, and each
edge gets a Kernel 3 weight ``w``, uniform in [0, 1).  Self-loops and
duplicate edges are kept, as the specification keeps them.  Each undirected
edge is stored in both directions, with its one weight.  The edge table has
the paper's layout (``id`` a permutation of the rows, ``from``, ``to``,
``name`` as 4 float32) plus ``w``.

The graph (edges, labels, weights) comes from the configuration's
``graph_seed``, as a Graph500 run times all its search keys on one graph, so
every run does the same work; the run's seed draws the order of the rows,
``id`` and ``name``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _kronecker(graph_key, key, scale: int, edgefactor: int, a: float,
               b: float, c: float) -> dict:
    m = edgefactor << scale
    k_bits, k_perm, k_w = jax.random.split(graph_key, 3)
    k_rows, k_id, k_name = jax.random.split(key, 3)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab

    def level(i, ij):
        u = jax.random.uniform(jax.random.fold_in(k_bits, i), (2, m))
        ii = u[0] > ab
        jj = u[1] > jnp.where(ii, c_norm, a_norm)
        return (ij[0] | (ii.astype(jnp.int32) << i),
                ij[1] | (jj.astype(jnp.int32) << i))

    zero = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zero, zero))
    label = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
    src, dst = label[src], label[dst]
    w = jax.random.uniform(k_w, (m,), jnp.float32)
    rows = jax.random.permutation(k_rows, 2 * m)
    return {"id": jax.random.permutation(k_id, 2 * m).astype(jnp.int32),
            "from": jnp.concatenate([src, dst])[rows],
            "to": jnp.concatenate([dst, src])[rows],
            "name": jax.random.normal(k_name, (2 * m, 4), jnp.float32),
            "w": jnp.concatenate([w, w])[rows]}


def generate(params: dict, seed: int) -> tuple[dict, int]:
    """Device columns of the edge table and the vertex count."""
    scale = int(params["scale"])
    cols = _kronecker(seed_key(int(params["graph_seed"])), seed_key(seed),
                      scale, int(params["edgefactor"]), float(params["a"]),
                      float(params["b"]), float(params["c"]))
    return cols, 1 << scale
