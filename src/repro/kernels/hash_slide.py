"""Production sliding-hash SpKAdd kernel — the paper's sort-free winner.

The paper's headline result (Tables 3/4) is that hash-based SpKAdd attains
both the computational and the I/O lower bounds and beats sort-based
accumulation whenever the compression factor is low, using the hash-vector
technique of Nagasaka et al. (KNL SpGEMM). Every other engine regime pays
the plan's stable sort over the concatenated stream *before* it
accumulates; this kernel pays **zero sorts before compaction**:

- Linear-probing tables live in VMEM output blocks, one ``(rows, 128)``
  table per (batch, output part); a probe reads and rewrites one 128-slot
  row under a lane mask, so Mosaic never stores a scalar to VMEM. Grid ``(B, parts, num_chunks)`` with the chunk axis
  innermost, so a part's table stays resident while the whole input stream
  slides past it (the revisited-output-block pattern from partition.py).
- Each nonzero is inserted-or-accumulated **in stream order**: slot values
  start at 0.0 and each duplicate adds on top, so the per-key value is the
  left fold of that key's stream occurrences from an f32 zero — exactly the
  canonical-PaddedCOO fold order every regime is pinned to. Insertion order
  preserves it; no sort is needed for correctness, only for final layout.
- Tables are sized by ``hash_accum.hash_table_size`` (spkaddlint SPK107):
  power of two, load factor <= 0.5, probes bounded by ``table_size``.
- Compaction to canonical order (sorted distinct keys, sentinel padding)
  happens exactly once at the very end, in the engine — the single counted
  stable sort of a ``hash`` dispatch.

When ``parts == 1`` (the full table fits the VMEM budget — the common case
the cost model gates on), every input chunk is DMA'd exactly once and each
nonzero costs one expected-O(1) probe chain: both paper lower bounds at
once, with no sort anywhere. When the key space is too wide, the stream is
re-read once per part (``parts * num_chunks`` chunk loads) with each part
covering ``table_size // 2`` keys so the load-factor bound is structural.

Per-element probing serializes VMEM round-trips, so wide-lane folds can
still win at high compression factors — the cost model arbitrates
(``hash_max_compression`` vs ``vec``); see DESIGN.md §4.4.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import pallas as pl
from repro.compat import pallas_tpu as pltpu
from repro.kernels import LANES, VMEM_LIMIT_BYTES
from repro.kernels.hash_accum import HASH_PRIME, hash_table_size

__all__ = [
    "hash_table_size",
    "hash_slide_raw",
    "modeled_insert_stats",
]


def _probe_insert(tkeys_ref, tvals_ref, key, val, *, table_size: int):
    """Insert-or-accumulate one (key, val) into the part's VMEM table.

    The table is ``(rows, width)``: slot ``h`` sits at row ``h // width``,
    lane ``h % width``. Linear probing scans a whole row per step: the
    first lane at or after the probe position whose key is empty or equal
    to ``key`` is the slot — the same slot a one-slot-at-a-time probe would
    reach — and the row is rewritten under a one-lane mask. The probe
    ``while_loop`` is bounded by ``rows + 1`` row steps (spkaddlint SPK107):
    at load factor <= 0.5 it ends long before.
    """
    rows, width = tkeys_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    h0 = ((key.astype(jnp.uint32) * jnp.uint32(HASH_PRIME))
          & jnp.uint32(table_size - 1)).astype(jnp.int32)

    def cond(carry):
        _, _, steps, done = carry
        return jnp.logical_not(done) & (steps <= rows)

    def body(carry):
        r, start, steps, _ = carry
        tk = tkeys_ref[pl.ds(r, 1), :]
        hit = (lane >= start) & ((tk == -1) | (tk == key))
        first = jnp.min(jnp.where(hit, lane, width))
        found = first < width

        @pl.when(found)
        def _write():
            at = lane == first
            tv = tvals_ref[pl.ds(r, 1), :]
            tkeys_ref[pl.ds(r, 1), :] = jnp.where(at, key, tk)
            tvals_ref[pl.ds(r, 1), :] = jnp.where(at, tv + val, tv)

        return (r + 1) % rows, jnp.int32(0), steps + 1, found

    jax.lax.while_loop(cond, body,
                       (h0 // width, h0 % width, jnp.int32(0), False))


def _slide_kernel(keys_ref, vals_ref, tkeys_ref, tvals_ref, *, mn: int,
                  part_span: int, chunk: int):
    p = pl.program_id(1)
    c = pl.program_id(2)
    table_size = tkeys_ref.shape[0] * tkeys_ref.shape[1]

    @pl.when(c == 0)
    def _init():
        tkeys_ref[...] = jnp.full(tkeys_ref.shape, -1, jnp.int32)
        tvals_ref[...] = jnp.zeros(tvals_ref.shape, jnp.float32)

    lo = p * part_span

    def insert(e, carry):
        k = keys_ref[e]

        @pl.when((k >= lo) & (k - lo < part_span) & (k < mn))
        def _do():
            _probe_insert(tkeys_ref, tvals_ref, k, vals_ref[e],
                          table_size=table_size)

        return carry

    jax.lax.fori_loop(0, chunk, insert, 0)


def hash_slide_raw(keys: jax.Array, vals: jax.Array, *, mn: int,
                   table_size: int, part_span: int, parts: int, chunk: int,
                   interpret: bool = True):
    """Accumulate batched streams into per-part hash tables.

    ``keys``/``vals`` are ``(B, cap)`` with ``cap`` a multiple of ``chunk``;
    keys ``>= mn`` are sentinels and never inserted. Returns raw tables
    ``(B, parts * table_size)`` (int32 keys, -1 = empty; f32 values), with
    part ``p`` owning keys in ``[p * part_span, (p + 1) * part_span)`` —
    concatenated part tables are key-range ordered, so one final stable
    sort yields the canonical layout. Stream chunks are read as scalars
    from SMEM; each part's table is a ``(rows, 128)`` VMEM block.
    """
    if keys.ndim != 2 or keys.shape != vals.shape:
        raise ValueError(f"keys/vals must be matching (B, cap) streams, got "
                         f"{keys.shape} vs {vals.shape}")
    B, cap = keys.shape
    if cap % chunk != 0:
        raise ValueError(f"cap {cap} must be a multiple of chunk {chunk}")
    if table_size & (table_size - 1) != 0:
        raise ValueError("table size must be 2^q")
    if table_size < 2 * min(part_span, cap):
        raise ValueError(
            f"table_size {table_size} violates load factor <= 0.5 for "
            f"part_span {part_span} / cap {cap} "
            f"(need >= {2 * min(part_span, cap)})")
    if part_span * parts < mn:
        raise ValueError(f"parts {parts} x span {part_span} must cover "
                         f"key space {mn}")
    num_chunks = cap // chunk
    width = min(table_size, LANES)
    rows = table_size // width

    stream = pl.BlockSpec((None, None, chunk), lambda b, p, c: (b, 0, c),
                          memory_space=pltpu.SMEM)
    table = pl.BlockSpec((None, rows, width),
                         lambda b, p, c: (b * parts + p, 0, 0))
    kernel = functools.partial(_slide_kernel, mn=mn, part_span=part_span,
                               chunk=chunk)
    tkeys, tvals = pl.pallas_call(
        kernel,
        grid=(B, parts, num_chunks),
        in_specs=[stream, stream],
        out_specs=[table, table],
        out_shape=[
            jax.ShapeDtypeStruct((B * parts, rows, width), jnp.int32),
            jax.ShapeDtypeStruct((B * parts, rows, width), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(keys.astype(jnp.int32).reshape(B, 1, cap),
      vals.astype(jnp.float32).reshape(B, 1, cap))
    return (tkeys.reshape(B, parts * table_size),
            tvals.reshape(B, parts * table_size))


def modeled_insert_stats(keys, *, mn: int, table_size: int, part_span: int,
                         parts: int, chunk: int) -> dict:
    """Host-side oracle: replay the exact kernel hash/probe sequence.

    Models the paper's cost accounting for a hash dispatch at this
    geometry: one table touch per probe, ``inserts`` is the compute lower
    bound (one insert per valid nonzero), ``chunk_loads`` is the stream
    I/O (``parts`` passes) vs the one-pass lower bound, and
    ``load_factor_max`` certifies the <= 0.5 sizing invariant held.
    """
    from repro import obs

    flat = np.asarray(keys).reshape(-1).astype(np.int64)
    valid = flat[flat < mn]
    mask = table_size - 1
    inserts = 0
    probes_total = 0
    max_probes = 0
    occ_max = 0
    for p in range(parts):
        lo = p * part_span
        part_keys = valid[(valid >= lo) & (valid < lo + part_span)]
        table = np.full(table_size, -1, np.int64)
        occ = 0
        for k in part_keys:
            h = (int(k) * HASH_PRIME) & mask
            probes = 1
            while table[h] != -1 and table[h] != k and probes <= table_size:
                h = (h + 1) & mask
                probes += 1
            if table[h] == -1:
                occ += 1
            table[h] = k
            inserts += 1
            probes_total += probes
            max_probes = max(max_probes, probes)
            obs.histogram("kernels.hash_slide.probes").observe(probes)
        occ_max = max(occ_max, occ)

    cap = flat.shape[0] if keys is not None else 0
    num_chunks = max(1, math.ceil(max(cap, 1) / chunk))
    chunk_loads = parts * num_chunks
    stats = {
        "inserts": inserts,
        "probes": probes_total,
        "probes_per_insert": probes_total / max(inserts, 1),
        "max_probes": max_probes,
        "table_size": table_size,
        "parts": parts,
        "load_factor_max": occ_max / table_size,
        "chunk_loads": chunk_loads,
        "chunk_loads_lower_bound": num_chunks,
    }
    obs.gauge("kernels.hash_slide.inserts").set(inserts)
    obs.gauge("kernels.hash_slide.chunk_loads").set(chunk_loads)
    obs.gauge("kernels.hash_slide.load_factor_max").set(stats["load_factor_max"])
    return stats
