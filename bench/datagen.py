"""Corpus and queries from a seed: the benchmark's own copy of
``repro.data.synthetic.clustered_vectors`` (a Gaussian mixture: centres
N(0, 1), points centre + ``cluster_std`` * N(0, 1), queries drawn from the
same mixture), so that a change to the program cannot move the data.

Everything is made on the device in one jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a whole-number seed of any width (the low 32 bits
    seed the key, the bits above are folded in)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def clustered_vectors(key: jax.Array, n: int, d: int, n_queries: int,
                      n_clusters: int = 64, cluster_std: float = 1.0):
    """(x (n, d), q (n_queries, d)) float32 Gaussian-mixture corpus and
    held-out queries; the same draws as the program's generator (the fused
    centre + std * noise may round the last bit otherwise)."""
    kc, kx, ka, kq, kb = jax.random.split(key, 5)
    centers = jax.random.normal(kc, (n_clusters, d))
    assign = jax.random.randint(ka, (n,), 0, n_clusters)
    x = centers[assign] + cluster_std * jax.random.normal(kx, (n, d))
    q_assign = jax.random.randint(kb, (n_queries,), 0, n_clusters)
    q = centers[q_assign] + cluster_std * jax.random.normal(kq, (n_queries, d))
    return x.astype(jnp.float32), q.astype(jnp.float32)
