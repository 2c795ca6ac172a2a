"""Erdos-Renyi inputs: every key equally likely (the paper's uniform family).

Keys are int32 CSC keys ``col * m + row``, uniform over ``m * n``; values
are standard-normal f32. A matrix is a COO triple list as generated: two
triples may share a key, and SpKAdd sums them like any other duplicate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def triples(key: jax.Array, *, m: int, n: int, k: int, nnz: int,
            params: dict) -> tuple[jax.Array, jax.Array]:
    """``(keys, vals)``, each of shape ``(k, nnz)``: k matrices of ``nnz``
    triples. ``params`` holds the family's settings (none for ER)."""
    del params
    kk, kv = jax.random.split(key)
    keys = jax.random.randint(kk, (k, nnz), 0, m * n, dtype=jnp.int32)
    vals = jax.random.normal(kv, (k, nnz), jnp.float32)
    return keys, vals


def dense(key: jax.Array, *, n: int, density: float) -> jax.Array:
    """An ``n x n`` f32 matrix whose entries are nonzero with probability
    ``density`` each, standard normal where they are: ER placement, held as
    a dense array (the form SUMMA takes its operands in)."""
    kp, kv = jax.random.split(key)
    keep = jax.random.uniform(kp, (n, n)) < density
    vals = jax.random.normal(kv, (n, n), jnp.float32)
    return jnp.where(keep, vals, 0.0)
