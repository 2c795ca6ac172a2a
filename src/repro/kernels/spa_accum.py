"""Sliding blocked-SPA accumulation kernel — TPU adaptation of sliding hash.

**This is the legacy all-pairs grid.** Its ``(parts, num_chunks)`` launch
re-reads the entire concatenated stream once per row-part (the input index
map ignores the part index), so input traffic is ``parts × N`` — it
violates the paper's I/O lower bound whenever ``parts > 1``. The
production path is the one-pass stream-partitioned grid in
:mod:`repro.kernels.partition`, which reads each input chunk exactly once;
this module is kept as the fidelity baseline, for unsorted streams (the
partitioned grid requires a part-grouped stream), and for the oracle
comparisons in ``tests/test_vec_accum.py``.

Paper (Alg. 7/8): when the accumulator exceeds the last-level cache M, split
the row space into ``parts = ceil(bytes/M)`` and slide the table. Here the
fast memory is VMEM: the grid's first dimension slides a dense
``(block_rows, n)`` f32 accumulator tile down the row space, and the second
dimension streams chunks of the concatenated (key, val) input through VMEM.
The output tile stays VMEM-resident across the whole chunk sweep (the output
index map is constant in the chunk dimension — the standard Pallas
accumulation pattern), so every random accumulator access is a VMEM hit:
the paper's cache discipline with M := VMEM, minus its I/O discipline.

Keys are CSC-linearized (``key = col*m + row``); the sentinel ``m*n`` (or
anything >= m*n) marks padding and is dropped in-kernel.

The **in-tile fold is pluggable** (``fold=`` launch parameter): the
``serial`` / ``sort`` / ``onehot`` folds of :mod:`repro.kernels.vec_accum`,
the same ones the partitioned grid runs, here over a ``(block_rows, n)``
tile (slot ``(row - row_lo) * n + col``). ``kernels/ops.vec_accumulate``
pre-sorts the stream, which makes every fold bit-identical to the canonical
``compress_plan`` contract.

This grid runs under the Pallas interpreter only: a CPU reference that
refuses to launch on a TPU (``compat.require_interpreter``). Interpret mode
validates both folds bit-exactly against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.compat import pallas as pl
from repro.compat import require_interpreter
from repro.kernels import vec_accum as _vec


DEFAULT_CHUNK = 1024


def _spa_kernel(keys_ref, vals_ref, out_ref, *, m: int, n: int,
                block_rows: int, fold: str):
    """``m`` is the TRUE row count (keys are col*m+row); the grid may cover a
    padded row space (parts*block_rows >= m) — trailing rows just stay 0."""
    part = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    row_lo = part * block_rows

    def locate(keys):
        rows = keys % m
        valid = ((keys < m * n) & (rows >= row_lo)
                 & (rows < row_lo + block_rows))
        return valid, (rows - row_lo) * n + keys // m

    _vec.apply_fold(fold, keys_ref, vals_ref, out_ref, locate)


def spa_accumulate_raw(keys: jax.Array, vals: jax.Array, *, m: int, n: int,
                       block_rows: int, chunk: int = DEFAULT_CHUNK,
                       fold: str = "serial") -> jax.Array:
    """Scatter-accumulate (key, val) streams into a dense (m, n) f32 array.

    ``keys``/``vals`` must already be padded to a multiple of ``chunk`` with
    sentinel keys (>= m*n) and zero values. ``m`` must be a multiple of
    ``block_rows`` (pad rows upstream). ``fold`` selects the in-tile
    accumulation strategy (see module docstring); bit-identity with the
    canonical contract needs a stream pre-sorted by key (stable).
    Interpreter-only.
    """
    require_interpreter("spa_accumulate_raw")
    if keys.shape != vals.shape or keys.ndim != 1:
        raise ValueError(f"keys/vals must be matching 1-D streams, got "
                         f"{keys.shape} vs {vals.shape}")
    if keys.shape[0] % chunk != 0:
        raise ValueError("pad inputs to a chunk multiple")
    if fold not in _vec.FOLDS:
        raise ValueError(f"unknown fold {fold!r}; one of {_vec.FOLDS}")
    parts = (m + block_rows - 1) // block_rows
    m_pad = parts * block_rows
    num_chunks = keys.shape[0] // chunk

    kernel = functools.partial(_spa_kernel, m=m, n=n, block_rows=block_rows,
                               fold=fold)
    out = pl.pallas_call(
        kernel,
        grid=(parts, num_chunks),
        in_specs=[
            pl.BlockSpec((chunk,), lambda i, c: (c,)),
            pl.BlockSpec((chunk,), lambda i, c: (c,)),
        ],
        out_specs=pl.BlockSpec((block_rows, n), lambda i, c: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), jnp.float32),
        interpret=True,
    )(keys, vals)
    return out[:m]
