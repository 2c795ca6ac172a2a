"""Graph500 Kronecker (R-MAT) inputs whose key space is past int32.

The draw of ``gen/rmat.py`` (its quadrant choice per level, its
``scramble``), made one level at a time: ``rmat.edges`` draws every level
of every edge at once, a ``(scale, count)`` float32 array (5.9 GB at scale
22), where here each level's uniforms become one bit of the row and of the
column and are dropped, so memory stays O(edges). At ``m = n = 2**scale``,
``m * n`` passes 2**31 from scale 16 on, so a key is given as its two int32
words, column and row, in CSC order; values are standard-normal f32 and
repeated edges stay repeated triples, as in ``gen/rmat.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.gen.rmat import edges, scramble


def triples(key: jax.Array, *, m: int, n: int, k: int, nnz: int,
            params: dict):
    """``((cols, rows), vals)``, each of shape ``(k, nnz)``: the edge list
    of one scale-``log2(m)`` Kronecker graph, ``params["edgefactor"]``
    edges per vertex, in k batches of ``nnz`` edges, each key as its two
    int32 words."""
    if m != n or m & (m - 1):
        raise ValueError(f"R-MAT needs a square power-of-two shape, got "
                         f"{m} x {n}")
    if k * nnz != params["edgefactor"] * m:
        raise ValueError(f"k * nnz = {k * nnz} edges, but edgefactor "
                         f"{params['edgefactor']} at {m} vertices makes "
                         f"{params['edgefactor'] * m}")
    ke, ks, kv = jax.random.split(key, 3)
    scale = m.bit_length() - 1
    count = k * nnz

    def level(lvl, acc):
        rows, cols = acc
        r, c = edges(jax.random.fold_in(ke, lvl), scale=1, count=count,
                     a=params["a"], b=params["b"], c=params["c"])
        return rows | (r << lvl), cols | (c << lvl)

    zero = jnp.zeros((count,), jnp.int32)
    rows, cols = jax.lax.fori_loop(0, scale, level, (zero, zero))
    val0, val1 = jax.random.bits(ks, (2,), jnp.uint32)
    cols = scramble(cols, scale, val0, val1).reshape(k, nnz)
    rows = scramble(rows, scale, val0, val1).reshape(k, nnz)
    vals = jax.random.normal(kv, (k, nnz), jnp.float32)
    return (cols, rows), vals
