"""Graph500 Kronecker (R-MAT) inputs.

Graph500 specification, Kronecker generator: every edge descends
``scale = log2(m)`` levels, and at each level picks one quadrant of the
adjacency matrix, for its row and column bit together, with probabilities
``a, b, c, d`` (0.57, 0.19, 0.19, 0.05). The vertex labels are then
scrambled by a seeded bijection, one for rows and columns alike, so that
the heavy vertices do not sit at the low keys: the Graph500 reference
generator's ``scramble`` (add, multiply by an odd number, bit-reverse,
twice), here in 32-bit arithmetic. It is elementwise, so it costs the
device next to nothing, where a permutation table costs a gather. Keys and values are as in
``gen/er.py``: int32 CSC keys ``col * m + row``, standard-normal f32 values,
repeated edges kept as repeated triples.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def edges(key: jax.Array, *, scale: int, count: int, a: float, b: float,
          c: float) -> tuple[jax.Array, jax.Array]:
    """``(rows, cols)`` of ``count`` edges, before the vertex permutation.

    Level ``l`` sets bit ``l`` of the row and of the column: quadrant
    (0, 0) with probability ``a``, (0, 1) with ``b``, (1, 0) with ``c`` and
    (1, 1) with the rest."""
    u = jax.random.uniform(key, (scale, count))
    row_bit = u >= a + b
    col_bit = ((u >= a) & (u < a + b)) | (u >= a + b + c)
    weight = (1 << jnp.arange(scale, dtype=jnp.int32))[:, None]
    rows = jnp.sum(row_bit * weight, axis=0, dtype=jnp.int32)
    cols = jnp.sum(col_bit * weight, axis=0, dtype=jnp.int32)
    return rows, cols


def _bitreverse32(v: jax.Array) -> jax.Array:
    for shift, mask in ((1, 0x55555555), (2, 0x33333333), (4, 0x0F0F0F0F),
                        (8, 0x00FF00FF)):
        v = ((v >> shift) & mask) | ((v & mask) << shift)
    return (v >> 16) | (v << 16)


def scramble(v: jax.Array, scale: int, val0: jax.Array,
             val1: jax.Array) -> jax.Array:
    """A bijection of ``[0, 2**scale)`` chosen by two uint32 values. The low
    ``scale`` bits of ``(v + s) * odd`` depend only on those of ``v`` and
    are a bijection of them; the bit reversal then moves them to the top."""
    v = v.astype(jnp.uint32) + (val0 + val1)
    v = v * (val0 | jnp.uint32(0x11493211))
    v = _bitreverse32(v) >> (32 - scale)
    v = v * (val1 | jnp.uint32(0x02C843A5))
    v = _bitreverse32(v) >> (32 - scale)
    return v.astype(jnp.int32)


def triples(key: jax.Array, *, m: int, n: int, k: int, nnz: int,
            params: dict) -> tuple[jax.Array, jax.Array]:
    """``(keys, vals)``, each of shape ``(k, nnz)``: the edge list of one
    scale-``log2(m)`` Kronecker graph, ``params["edgefactor"]`` edges per
    vertex, in k batches of ``nnz`` edges."""
    if m != n or m & (m - 1):
        raise ValueError(f"R-MAT needs a square power-of-two shape, got "
                         f"{m} x {n}")
    if k * nnz != params["edgefactor"] * m:
        raise ValueError(f"k * nnz = {k * nnz} edges, but edgefactor "
                         f"{params['edgefactor']} at {m} vertices makes "
                         f"{params['edgefactor'] * m}")
    ke, ks, kv = jax.random.split(key, 3)
    scale = m.bit_length() - 1
    rows, cols = edges(ke, scale=scale, count=k * nnz, a=params["a"],
                       b=params["b"], c=params["c"])
    val0, val1 = jax.random.bits(ks, (2,), jnp.uint32)
    keys = (scramble(cols, scale, val0, val1) * m
            + scramble(rows, scale, val0, val1))
    vals = jax.random.normal(kv, (k, nnz), jnp.float32)
    return keys.reshape(k, nnz), vals
