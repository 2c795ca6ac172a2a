"""In-tile folds shared by the sliding accumulation grids.

A sliding grid (``kernels/partition.py``, and the legacy row-tiled grid in
``kernels/spa_accum.py``) keeps one accumulator tile of shape
``(rows, width)`` resident in VMEM and streams chunks of (key, val) pairs
past it. A fold adds one chunk into the tile. Every fold is called as
``fold(keys_ref, vals_ref, out_ref, locate)``, where ``locate(keys)`` maps
keys (a scalar or a vector) to ``(valid, slot)``: whether a key belongs to
the resident tile, and its flat row-major offset in it. Slot ``s`` lives at
row ``s // width``, lane ``s % width``.

The two folds are written for Mosaic, the TPU kernel compiler:

``serial``
    One read-modify-write of the slot's tile row per input element. Keys and
    values are read as scalars from SMEM; the row is updated under a lane
    mask. O(chunk) row round-trips through VMEM.

``onehot``
    The fold for small tiles. Keys and values arrive as one ``(1, chunk)``
    VMEM vector. A log-step scan (lane rolls) gives every element its rank
    inside its run of equal slots; round ``j`` then scatters the ``j``-th
    element of every run into the tile with one one-hot MXU matmul, so the
    tile update needs no serial stores. Rounds = the longest run in the
    chunk. Needs equal keys to be contiguous in the chunk, which a
    key-sorted stream guarantees.

Bit-identity with the canonical ``compress_plan`` contract (DESIGN.md
§3.3): every fold applies each contribution as ``acc[s] = acc[s] + v``, in
stream order, starting from the tile's current value. That is the left fold
``jax.ops.segment_sum`` performs over the stable-sorted stream, across chunk
boundaries included. The one-hot matmul is exact because each output cell
receives at most one nonzero product per round, at ``HIGHEST`` precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.compat import pallas as pl
from repro.compat import pallas_tpu as pltpu


def _lane_ids(width: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)


def serial_fold(keys_ref, vals_ref, out_ref, locate) -> None:
    """One masked tile-row read-modify-write per input element."""
    width = out_ref.shape[-1]
    lane = _lane_ids(width)

    def body(e, carry):
        valid, slot = locate(keys_ref[e])

        @pl.when(valid)
        def _add():
            r = slot // width
            row = out_ref[pl.ds(r, 1), :]
            out_ref[pl.ds(r, 1), :] = jnp.where(lane == slot % width,
                                                row + vals_ref[e], row)

        return carry

    jax.lax.fori_loop(0, keys_ref.shape[0], body, 0)


def run_offsets(slot: jax.Array, valid: jax.Array) -> jax.Array:
    """Rank of each element inside its run of equal slots, for ``(1, n)``
    vectors whose equal slots are contiguous. A log-step max-scan (lane
    rolls) carries the position of the latest run head; integer-only, so
    the scan order cannot perturb any float result."""
    n = slot.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, slot.shape, 1)
    head = valid & ((slot != pltpu.roll(slot, 1, 1)) | (pos == 0))
    start = jnp.where(head, pos, -1)
    d = 1
    while d < n:
        start = jnp.maximum(start,
                            jnp.where(pos >= d, pltpu.roll(start, d, 1), -1))
        d *= 2
    return pos - start


def onehot_fold(keys_ref, vals_ref, out_ref, locate) -> None:
    """Scatter the chunk into the tile with one one-hot matmul per rank."""
    rows, width = out_ref.shape
    keys = keys_ref[...]
    keys = keys.reshape(1, keys.size)
    valid, slot = locate(keys)
    vals = jnp.where(valid, vals_ref[...].reshape(keys.shape), 0.0)
    slot = jnp.where(valid, slot, 0)
    rank = run_offsets(slot, valid)
    rounds = jnp.max(jnp.where(valid, rank + 1, 0))
    row_of, lane_of = slot // width, slot % width
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    lane_ids = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)

    def add_rank(j, tile):
        sel = valid & (rank == j)
        a = jnp.where(sel & (row_ids == row_of), vals, 0.0)     # (rows, n)
        b = jnp.where(sel & (lane_ids == lane_of), 1.0, 0.0)    # (width, n)
        return tile + jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    out_ref[...] = jax.lax.fori_loop(0, rounds, add_rank, out_ref[...])


#: fold-mode registry the sliding grids dispatch on (static, per launch).
FOLDS = ("serial", "onehot")

#: folds that read keys/values as scalars, so their input blocks live in SMEM
SCALAR_FOLDS = ("serial",)

#: fold name -> in-tile fold fn, shared by the legacy row-tiled grid
#: (spa_accum.py) and the one-pass partitioned grid (partition.py).
FOLD_FNS = {"serial": serial_fold, "onehot": onehot_fold}


def apply_fold(fold: str, keys_ref, vals_ref, out_ref, locate) -> None:
    """Dispatch the in-tile fold by (static) name."""
    FOLD_FNS[fold](keys_ref, vals_ref, out_ref, locate)


# ---------------------------------------------------------------------------
# host-side store-count oracle (benchmark observability)
# ---------------------------------------------------------------------------

def chunk_store_counts(keys, *, m: int, n: int, block_rows: int,
                       chunk: int) -> dict:
    """Tile-row stores per fold for a stream on the legacy row-tiled grid
    (``(block_rows, n)`` tiles): ``serial`` stores once per element of every
    (part, chunk) cell, ``onehot`` never (MXU matmul).

    Host-side — benchmark/observability only, not a traced path.
    """
    parts = (m + block_rows - 1) // block_rows
    num_chunks = (max(len(keys), 1) + chunk - 1) // chunk
    return {"serial": parts * num_chunks * chunk, "onehot_fold": 0,
            "parts": parts, "num_chunks": num_chunks}
