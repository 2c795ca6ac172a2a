"""One-pass stream-partitioned sliding accumulation — the I/O-optimal grid.

The paper's headline result (Tables I/II) is that hash/sliding-hash SpKAdd
meets the lower bounds on *both* computation and I/O. The legacy sliding
grid (:mod:`repro.kernels.spa_accum`) meets the computation bound but not
the I/O bound: its ``(parts, num_chunks)`` launch re-reads the whole
concatenated stream once per part — ``parts × N`` input traffic. This
module restores the one-pass discipline of the paper's Alg. 8:

1. **One shared sort.** The accumulator is partitioned into key-aligned
   ranges (``part = key // part_elems``), so the composite partition key
   ``part * (m*n) + key`` is monotone in ``key`` and the canonical
   ``compress_plan`` sort doubles as the partition sort
   (:func:`repro.core.sparse.plan_and_partition`). The `vec` regime's old
   duplicate sort (plan + in-wrapper pre-sort) collapses to one.

2. **CSR-style step schedule.** Binary search over the sorted stream yields
   per-part element ranges; these flatten into per-step ``(chunk, part)``
   tables (:func:`repro.core.sparse.partition_steps`) fed to the kernel via
   scalar prefetch, so the grid's index maps become data-dependent.

3. **One-touch launch.** The grid is ``(B, max_steps)``; step ``t`` reads
   input chunk ``chunk_id[b, t]`` and accumulates into the VMEM-resident
   tile of part ``part_id[b, t]``. Both tables are non-decreasing, so
   output-tile revisits are *consecutive* (the legal Pallas accumulation
   pattern: the tile stays resident until the part changes) and an input
   chunk is DMA'd only when ``chunk_id`` changes — **total input loads =
   number of non-empty chunks**, not ``parts × num_chunks``.
   :func:`modeled_chunk_loads` is the host-side oracle for that claim
   (``benchmarks/spkadd_io.py`` emits it as ``BENCH_spkadd_io.json``).

The leading batch grid dimension makes the launch batchable: B independent
sorted streams with per-batch step tables run in one ``pallas_call``, which
is what lets ``engine.spkadd_batched`` keep a `vec` selection on the Pallas
path instead of silently downgrading to the dense-SPA scatter.

In-tile folds are shared with the legacy grid (``vec_accum.FOLD_FNS``:
``serial`` / ``onehot``). A part's tile is ``(part_elems //
width, width)`` with ``width = 128`` lanes (the geometry keeps
``part_elems`` a lane multiple), so the kernel's output, read as
``(B, parts * rows, width)``, *is* the key-ordered accumulator:
``acc[b, key // width, key % width]`` is key's value — no transpose
epilogue. Bit-identity with the canonical contract holds because the
stream is in stable key order: each key's duplicates are contiguous, in
stream order, and span only consecutive steps of one part (DESIGN.md §4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import pallas as pl
from repro.compat import pallas_tpu as pltpu
from repro.kernels import LANES, VMEM_LIMIT_BYTES
from repro.kernels import vec_accum as _vec


#: lane multiple the geometry keeps ``part_elems`` at.
LANE_MULT = LANES


def tile_width(elems: int) -> int:
    """Lane width of a ``elems``-slot tile: full 128-lane rows when
    ``elems`` is a lane multiple, else one row (small test geometries)."""
    return LANES if elems % LANES == 0 else elems


def _partitioned_kernel(chunk_ref, part_ref, keys_ref, vals_ref, out_ref, *,
                        mn: int, part_elems: int, parts: int, fold: str):
    """Grid step (b, t): fold chunk ``chunk_id[b, t]`` into the tile of part
    ``part_id[b, t]``. The tile is zeroed when the (batch, part) block first
    becomes resident; keys outside the part (other parts' keys in a boundary
    chunk, sentinels, every key of a padded step) contribute nothing."""
    b = pl.program_id(0)
    t = pl.program_id(1)
    p_raw = part_ref[b, t]
    p = jnp.minimum(p_raw, parts - 1)
    prev = jnp.minimum(part_ref[b, jnp.maximum(t, 1) - 1], parts - 1)

    @pl.when(jnp.logical_or(t == 0, prev != p))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lo = p * part_elems
    hi = jnp.where(p_raw < parts, jnp.minimum(lo + part_elems, mn), lo)
    _vec.apply_fold(fold, keys_ref, vals_ref, out_ref,
                    lambda k: ((k >= lo) & (k < hi), k - lo))


def partitioned_accumulate_raw(keys: jax.Array, vals: jax.Array,
                               chunk_id: jax.Array, part_id: jax.Array, *,
                               mn: int, part_elems: int, parts: int,
                               chunk: int, fold: str = "serial",
                               interpret: bool = True) -> jax.Array:
    """One-pass partitioned scatter-accumulate -> ``(B, parts * rows, width)``.

    ``keys``/``vals`` are ``(B, cap_pad)`` **sorted** streams (ascending,
    sentinel-padded to a chunk multiple); ``chunk_id``/``part_id`` are the
    ``(B, max_steps)`` step tables from ``sparse.partition_steps``. In the
    result, ``acc[b, key // width, key % width]`` is the accumulated value
    of ``key`` (``width = tile_width(part_elems)``). The serial fold reads
    its chunks from SMEM, the one-hot fold from VMEM.
    """
    if keys.ndim != 2 or keys.shape != vals.shape:
        raise ValueError(f"keys/vals must be matching 2-D streams, got "
                         f"{keys.shape} vs {vals.shape}")
    if chunk_id.shape != part_id.shape or chunk_id.shape[0] != keys.shape[0]:
        raise ValueError("step tables must share shape and batch the streams")
    if keys.shape[1] % chunk != 0:
        raise ValueError("pad streams to a chunk multiple")
    if fold not in _vec.FOLDS:
        raise ValueError(f"unknown fold {fold!r}; one of {_vec.FOLDS}")
    B, cap_pad = keys.shape
    max_steps = chunk_id.shape[1]
    width = tile_width(part_elems)
    rows = part_elems // width

    def stream_index(b, t, c_ref, p_ref):
        return b, 0, c_ref[b, t]

    if fold in _vec.SCALAR_FOLDS:
        stream = pl.BlockSpec((None, None, chunk), stream_index,
                              memory_space=pltpu.SMEM)
    else:
        stream = pl.BlockSpec((None, 1, chunk), stream_index)
    kernel = functools.partial(_partitioned_kernel, mn=mn,
                               part_elems=part_elems, parts=parts, fold=fold)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_steps),
        in_specs=[stream, stream],
        out_specs=pl.BlockSpec(
            (None, rows, width),
            lambda b, t, c_ref, p_ref: (
                b * parts + jnp.minimum(p_ref[b, t], parts - 1), 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * parts, rows, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(chunk_id, part_id, keys.reshape(B, 1, cap_pad),
      vals.reshape(B, 1, cap_pad))
    return out.reshape(B, parts * rows, width)


# ---------------------------------------------------------------------------
# host-side I/O oracle (benchmark observability)
# ---------------------------------------------------------------------------

def modeled_chunk_loads(keys, *, mn: int, part_elems: int, parts: int,
                        chunk: int) -> dict:
    """Modeled input-chunk loads for a stream at a given launch geometry.

    The one-pass count is derived from the **actual step tables the kernel
    launches with** (``sparse.partition_steps`` on the sorted padded
    stream), not a reimplementation — a chunk is loaded when ``chunk_id``
    differs from the previous step's (the Pallas pipelining rule: an
    unchanged input block index is not re-fetched), so this oracle cannot
    drift from the schedule it claims to model.

    Returns per-strategy load counts:
    ``onepass``           the partitioned grid (this module);
    ``legacy_all_pairs``  the all-pairs re-reading pattern at THIS
                          partition geometry (``parts × num_chunks``) —
                          the counterfactual, distinct from the actual
                          row-tiled legacy kernel's own geometry, which
                          ``benchmarks/spkadd_io.py`` models separately;
    ``lower_bound``       the paper's I/O bound at this geometry — each
                          non-empty chunk read once (empty = the
                          all-sentinel tail).
    """
    from repro.core.sparse import partition_steps

    keys = np.asarray(keys)
    cap = len(keys)
    cap_pad = ((max(cap, 1) + chunk - 1) // chunk) * chunk
    num_chunks = cap_pad // chunk
    keys_p = np.full(cap_pad, mn, dtype=np.int32)
    keys_p[:cap] = np.minimum(keys, mn)
    keys_s = np.sort(keys_p, kind="stable")
    nvalid = int(np.searchsorted(keys_s, mn, side="left"))
    nonempty_chunks = max(1, -(-nvalid // chunk)) if nvalid else 1

    steps = partition_steps(jnp.asarray(keys_s), mn=mn,
                            part_elems=part_elems, parts=parts, chunk=chunk)
    chunk_id = np.asarray(steps.chunk_id)
    part_id = np.asarray(steps.part_id)
    loads = 1 + int((np.diff(chunk_id) != 0).sum())
    from repro import obs
    obs.gauge("kernels.partition.modeled.onepass_loads").set(loads)
    obs.gauge("kernels.partition.modeled.lower_bound").set(nonempty_chunks)
    obs.gauge("kernels.partition.modeled.all_pairs_loads").set(
        parts * num_chunks)
    return {
        "onepass": loads,
        "legacy_all_pairs": parts * num_chunks,
        "lower_bound": nonempty_chunks,
        "num_chunks": num_chunks,
        "parts": parts,
        "steps": int((part_id < parts).sum()),
    }
