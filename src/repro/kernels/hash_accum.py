"""Faithful hash-table SpKAdd kernel (paper Alg. 5 + symbolic Alg. 6).

Multiplicative masking hash ``h = (a*key) & (2^q - 1)`` with linear probing,
table resident in VMEM (the paper's LLC), one insert per input nonzero. The
probe loop is a ``while_loop`` whose body reads the table ref and whose carry
decides termination — the canonical Pallas pattern for data-dependent probing.

This kernel exists to reproduce the paper's algorithm *as published*: it is
bit-faithful and runs under the Pallas interpreter only — a CPU reference
that refuses to launch on a TPU (``compat.require_interpreter``). Its scalar
VMEM stores are exactly what Mosaic rejects; the engine's hash regime is the
row-probing sliding kernel in ``hash_slide.py``.

Table sizing follows the paper: a power of two strictly greater than the
worst-case distinct-key count, kept at load factor <= 0.5 so expected probes
stay O(1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.compat import pallas as pl
from repro.compat import require_interpreter

HASH_PRIME = 2654435761  # Knuth multiplicative constant


def hash_table_size(distinct_bound: int) -> int:
    """The ONE table-sizing rule every hash kernel shares (spkaddlint
    SPK107): the smallest power of two ``>= 2 * distinct_bound``, so the
    load factor can never exceed 0.5 and expected probes stay O(1).

    ``distinct_bound`` is the worst-case distinct-key count the table must
    absorb (stream capacity for the faithful kernel, ``min(cap, part_span)``
    per part for the sliding kernel).
    """
    size = 1
    while size < 2 * max(int(distinct_bound), 1):
        size *= 2
    return size


def _probe(table_keys_ref, key: jax.Array, mask: jax.Array, *,
           table_size: int):
    """Linear-probe for ``key``; returns the terminal slot (empty-or-match).

    The probe ``while_loop`` carries a step counter bounded by
    ``table_size`` (spkaddlint SPK107): at load factor <= 0.5 the probe
    chain always hits an empty slot first, but the bound makes termination
    a static property rather than a sizing-discipline consequence — an
    undersized table degrades to a bounded scan instead of a hang.
    """
    prime = jnp.asarray(HASH_PRIME, jnp.uint32)
    h0 = ((key.astype(jnp.uint32) * prime) & mask).astype(jnp.int32)

    def cond(carry):
        _, steps, done = carry
        return jnp.logical_not(done) & (steps < table_size)

    def body(carry):
        h, steps, _ = carry
        tk = table_keys_ref[h]
        done = (tk == -1) | (tk == key)
        h_next = jnp.where(done, h, (h + 1) & mask.astype(jnp.int32))
        return h_next, steps + jnp.int32(1), done

    h_final, _, _ = jax.lax.while_loop(cond, body,
                                       (h0, jnp.int32(0), False))
    return h_final


def _hash_kernel(keys_ref, vals_ref, tkeys_ref, tvals_ref, *, nnz_cap: int,
                 table_size: int, sent: int):
    mask = jnp.uint32(table_size - 1)
    tkeys_ref[...] = jnp.full((table_size,), -1, jnp.int32)
    tvals_ref[...] = jnp.zeros((table_size,), jnp.float32)

    def insert(e, _):
        k = keys_ref[e]
        v = vals_ref[e]

        @pl.when(k != sent)
        def _do():
            h = _probe(tkeys_ref, k, mask, table_size=table_size)
            tkeys_ref[h] = k
            tvals_ref[h] = tvals_ref[h] + v

        return 0

    jax.lax.fori_loop(0, nnz_cap, insert, 0)


def hash_accumulate_raw(keys: jax.Array, vals: jax.Array, *, sent: int,
                        table_size: int | None = None):
    """Insert every (key, val) into a VMEM hash table. Returns the raw table
    (tkeys == -1 marks empty slots). Interpreter-only."""
    require_interpreter("hash_accumulate_raw")
    if keys.ndim != 1 or keys.shape != vals.shape:
        raise ValueError(f"keys/vals must be matching 1-D streams, got "
                         f"{keys.shape} vs {vals.shape}")
    cap = keys.shape[0]
    if table_size is None:
        table_size = hash_table_size(cap + 1)
    if table_size & (table_size - 1) != 0:
        raise ValueError("table size must be 2^q")

    kernel = functools.partial(_hash_kernel, nnz_cap=cap,
                               table_size=table_size, sent=sent)
    tkeys, tvals = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(keys.shape, lambda: (0,)),
                  pl.BlockSpec(vals.shape, lambda: (0,))],
        out_specs=[pl.BlockSpec((table_size,), lambda: (0,)),
                   pl.BlockSpec((table_size,), lambda: (0,))],
        out_shape=[jax.ShapeDtypeStruct((table_size,), jnp.int32),
                   jax.ShapeDtypeStruct((table_size,), jnp.float32)],
        interpret=True,
    )(keys, vals.astype(jnp.float32))
    return tkeys, tvals


def _hash_symbolic_kernel(keys_ref, nz_ref, tkeys_ref, *, nnz_cap: int,
                          table_size: int, sent: int):
    """Paper Alg. 6: count distinct keys; table stores keys only (4 B/entry,
    half the addition-phase footprint — the paper's reason the symbolic phase
    benefits most from sliding)."""
    mask = jnp.uint32(table_size - 1)
    tkeys_ref[...] = jnp.full((table_size,), -1, jnp.int32)
    nz_ref[0] = jnp.int32(0)

    def insert(e, _):
        k = keys_ref[e]

        @pl.when(k != sent)
        def _do():
            h = _probe(tkeys_ref, k, mask, table_size=table_size)
            tk = tkeys_ref[h]

            @pl.when(tk == -1)
            def _new():
                tkeys_ref[h] = k
                nz_ref[0] = nz_ref[0] + 1

        return 0

    jax.lax.fori_loop(0, nnz_cap, insert, 0)


def hash_symbolic_raw(keys: jax.Array, *, sent: int,
                      table_size: int | None = None) -> jax.Array:
    """Distinct-key count via the faithful hash symbolic phase.
    Interpreter-only."""
    require_interpreter("hash_symbolic_raw")
    cap = keys.shape[0]
    if table_size is None:
        table_size = hash_table_size(cap + 1)

    kernel = functools.partial(_hash_symbolic_kernel, nnz_cap=cap,
                               table_size=table_size, sent=sent)
    nz, _ = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(keys.shape, lambda: (0,))],
        out_specs=[pl.BlockSpec((1,), lambda: (0,)),
                   pl.BlockSpec((table_size,), lambda: (0,))],
        out_shape=[jax.ShapeDtypeStruct((1,), jnp.int32),
                   jax.ShapeDtypeStruct((table_size,), jnp.int32)],
        interpret=True,
    )(keys)
    return nz[0]
