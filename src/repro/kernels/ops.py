"""jit'd public wrappers around the Pallas kernels.

These handle padding/alignment (chunk multiples, row-block multiples, power-of
-two tables) and the compaction from raw kernel outputs back to the PaddedCOO
calling convention, so callers never see kernel launch geometry.
"""
from __future__ import annotations

import functools
import typing as _t

import jax
import jax.numpy as jnp

from repro import obs
from repro.compat import interpret_kernels
from repro.core.sparse import next_pow2 as _next_pow2
from repro.core.sparse import stable_sort_pairs as _stable_sort_pairs
from repro.kernels import VMEM_BUDGET_BYTES
from repro.kernels import hash_accum as _hash
from repro.kernels import spa_accum as _spa
from repro.kernels import vec_accum as _vec


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _round_down(x: int, mult: int) -> int:
    return (x // mult) * mult


def choose_block_rows(m: int, n: int, vmem_budget_bytes: int,
                      dtype_bytes: int = 4, lane_mult: int = 8) -> int:
    """Paper Alg. 7 line 3, with M := VMEM: parts = ceil(rows·n·b / M);
    block_rows = the largest sublane multiple that *fits the budget*
    (floored at ``lane_mult`` — the hardware minimum tile, the one case
    allowed to exceed a sub-minimal budget).

    Rounding is **down**: rounding the block up to the lane multiple could
    exceed ``budget_rows`` and overflow VMEM on real hardware (regression:
    a 9-row budget used to produce a 16-row tile).
    """
    budget_rows = max(1, vmem_budget_bytes // max(1, n * dtype_bytes))
    block = min(_round_up(m, lane_mult), budget_rows)
    return max(lane_mult, _round_down(block, lane_mult))


@functools.partial(jax.jit, static_argnames=("m", "n", "block_rows",
                                             "vmem_budget_bytes", "chunk"))
def spa_accumulate(keys: jax.Array, vals: jax.Array, *, m: int, n: int,
                   block_rows: int | None = None,
                   vmem_budget_bytes: int = 16 * 1024 * 1024,
                   chunk: int = _spa.DEFAULT_CHUNK) -> jax.Array:
    """Sliding blocked-SPA accumulate -> dense (m, n) f32.

    Pads the input stream to a chunk multiple (sentinel keys) and the row
    space to a block multiple, launches the sliding kernel, crops the result.
    """
    if block_rows is None:
        block_rows = choose_block_rows(m, n, vmem_budget_bytes)
    block_rows = min(block_rows, _round_up(m, 8))
    cap = keys.shape[0]
    cap_pad = _round_up(max(cap, 1), chunk)
    sent = jnp.int32(m * n)  # dropped in-kernel (keys < m*n is the test)
    keys_p = jnp.full((cap_pad,), sent, jnp.int32).at[:cap].set(
        jnp.where(keys < m * n, keys, sent))
    vals_p = jnp.zeros((cap_pad,), jnp.float32).at[:cap].set(
        jnp.where(keys < m * n, vals.astype(jnp.float32), 0.0))
    return _spa.spa_accumulate_raw(keys_p, vals_p, m=m, n=n,
                                   block_rows=block_rows, chunk=chunk)


def spa_accumulate_flat(keys: jax.Array, vals: jax.Array, *, m: int, n: int,
                        block_rows: int | None = None,
                        vmem_budget_bytes: int = 16 * 1024 * 1024,
                        chunk: int = _spa.DEFAULT_CHUNK) -> jax.Array:
    """Sliding blocked-SPA accumulate -> flat (m*n,) f32 in *key order*
    (col-major), so ``flat[key]`` is the accumulated value of ``key``.

    The form the regime engine consumes: it gathers canonical output values
    straight out of the accumulator without a dense (m, n) detour.
    """
    dense = spa_accumulate(keys, vals, m=m, n=n, block_rows=block_rows,
                           vmem_budget_bytes=vmem_budget_bytes, chunk=chunk)
    return dense.T.reshape(-1)


#: tiles at or below this many elements use the one-hot MXU fold by default
#: (mirrors ``engine.DEFAULT_COST_MODEL["vec_onehot_max_block_elems"]``).
DEFAULT_ONEHOT_MAX_BLOCK_ELEMS = 4096


def fold_working_set_bytes(fold: str, *, tile_elems: int, chunk: int) -> int:
    """Estimated VMEM working set of ONE grid step of a sliding/partitioned
    launch — the single formula shared by the fold choosers here and in the
    engine, and by the static VMEM-budget rule (``repro.analysis.vmem``), so
    the analyzer proves exactly the budget the runtime enforces.

    Counts both pipeline buffers of the f32 output tile (Pallas
    double-buffers output blocks too), the double-buffered int32-key/f32-val
    input blocks (two in-flight ``(chunk,)`` pairs, 8 B per element), and —
    for the one-hot fold only — its per-round ``(rows, chunk)`` value matrix
    and ``(128, chunk)`` lane one-hot plus their masks (8 B per cell). The
    scalar folds keep one tile row in registers, so they add no VMEM term.
    """
    out_tile = 2 * tile_elems * 4
    inputs = 2 * chunk * 8
    inter = chunk * (tile_elems // 128 + 128) * 8 if fold == "onehot" else 0
    return out_tile + inputs + inter


def vec_launch_geometry(cap: int, *, m: int, n: int,
                        block_rows: int | None = None,
                        vmem_budget_bytes: int = 16 * 1024 * 1024,
                        chunk: int | None = None) -> tuple[int, int]:
    """(block_rows, chunk) the vec launch uses for a ``cap``-long stream —
    the single source of truth shared by :func:`vec_accumulate` and the
    store-count oracle, so the oracle can never drift from the kernel."""
    if block_rows is None:
        block_rows = choose_block_rows(m, n, vmem_budget_bytes)
    block_rows = min(block_rows, _round_up(m, 8))
    if chunk is None:
        chunk = min(_spa.DEFAULT_CHUNK, _next_pow2(max(cap, 8)))
    return block_rows, chunk


@functools.partial(jax.jit, static_argnames=("m", "n", "fold", "block_rows",
                                             "vmem_budget_bytes", "chunk",
                                             "onehot_max_block_elems"))
def vec_accumulate(keys: jax.Array, vals: jax.Array, *, m: int, n: int,
                   fold: str = "auto", block_rows: int | None = None,
                   vmem_budget_bytes: int = 16 * 1024 * 1024,
                   chunk: int | None = None,
                   onehot_max_block_elems: int = DEFAULT_ONEHOT_MAX_BLOCK_ELEMS
                   ) -> jax.Array:
    """Lane-parallel sliding accumulate -> dense (m, n) f32.

    Same sliding grid as :func:`spa_accumulate`, but the in-tile fold is one
    of the folds from :mod:`repro.kernels.vec_accum`: ``fold="serial"`` (one
    tile-row store per element) or ``fold="onehot"`` (one-hot MXU fold,
    zero serial stores).
    ``fold="auto"`` picks ``onehot`` when the tile has at most
    ``onehot_max_block_elems`` elements (the matmul's FLOPs stay cheap) and
    ``serial`` otherwise.

    The stream is **pre-sorted by key (stable)** before launch. That makes
    the fold bit-identical to the canonical ``compress_plan`` contract
    (stream-order per-key sums) regardless of the input order — the stable
    sort is exactly the plan's, so duplicates keep their stream order and
    runs never fragment across in-chunk masking.
    """
    sent = jnp.int32(m * n)
    valid = keys < m * n
    keys_c = jnp.where(valid, keys, sent).astype(jnp.int32)
    vals_c = jnp.where(valid, vals.astype(jnp.float32), 0.0)
    keys_s, vals_s = _stable_sort_pairs(keys_c, vals_c)

    cap = keys.shape[0]
    block_rows, chunk = vec_launch_geometry(
        cap, m=m, n=n, block_rows=block_rows,
        vmem_budget_bytes=vmem_budget_bytes, chunk=chunk)
    if fold == "auto":
        # the WHOLE step working set (both tile buffers, double-buffered
        # inputs, the one-hot intermediates) must fit the VMEM budget
        onehot_ws = fold_working_set_bytes(
            "onehot", tile_elems=block_rows * n, chunk=chunk)
        fold = "onehot" if (block_rows * n <= onehot_max_block_elems
                            and onehot_ws <= vmem_budget_bytes) \
            else "serial"

    cap_pad = _round_up(max(cap, 1), chunk)
    keys_p = jnp.full((cap_pad,), sent, jnp.int32).at[:cap].set(keys_s)
    vals_p = jnp.zeros((cap_pad,), jnp.float32).at[:cap].set(vals_s)
    return _spa.spa_accumulate_raw(keys_p, vals_p, m=m, n=n,
                                   block_rows=block_rows, chunk=chunk,
                                   fold=fold)


def vec_accumulate_flat(keys: jax.Array, vals: jax.Array, *, m: int, n: int,
                        **kw) -> jax.Array:
    """:func:`vec_accumulate` -> flat (m*n,) f32 in key order (col-major),
    so ``flat[key]`` is the accumulated value of ``key`` — the form the
    regime engine's canonical gather consumes."""
    dense = vec_accumulate(keys, vals, m=m, n=n, **kw)
    return dense.T.reshape(-1)


def vec_store_counts(keys, *, m: int, n: int,
                     block_rows: int | None = None,
                     vmem_budget_bytes: int = 16 * 1024 * 1024,
                     chunk: int | None = None) -> dict:
    """Host-side serial-store counts (serial vs one-hot) for
    the launch geometry :func:`vec_accumulate` would use on this stream."""
    block_rows, chunk = vec_launch_geometry(
        len(keys), m=m, n=n, block_rows=block_rows,
        vmem_budget_bytes=vmem_budget_bytes, chunk=chunk)
    counts = _vec.chunk_store_counts(keys, m=m, n=n, block_rows=block_rows,
                                     chunk=chunk)
    obs.gauge("kernels.vec.stores.serial").set(counts["serial"])
    obs.gauge("kernels.vec.stores.onehot_fold").set(counts["onehot_fold"])
    return counts


# ---------------------------------------------------------------------------
# one-pass stream-partitioned launch (kernels/partition.py)
# ---------------------------------------------------------------------------

class PartitionGeometry(_t.NamedTuple):
    """Static launch geometry of the one-pass partitioned grid — the single
    source of truth shared by :func:`partitioned_accumulate_flat`, the
    engine, and the I/O oracle (``benchmarks/spkadd_io.py``), so the oracle
    can never drift from the kernel."""

    part_elems: int  # flat accumulator tile size (f32 elements)
    parts: int       # number of tiles covering m*n
    chunk: int       # input chunk length (power of two)
    num_chunks: int  # padded stream length / chunk
    max_steps: int   # static bound on (chunk, part) grid steps


def partitioned_launch_geometry(cap: int, *, m: int, n: int,
                                part_elems: int | None = None,
                                vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
                                chunk: int | None = None) -> PartitionGeometry:
    """Geometry the partitioned launch uses for a ``cap``-long stream.

    The whole launch working set is budgeted, not just the tile: the
    double-buffered input blocks (two in-flight ``(chunk,)`` key/value
    pairs, 8 bytes per element) get at most half of
    ``vmem_budget_bytes`` — ``chunk`` halves (staying a power of two,
    floored at 8) until they fit — and ``part_elems`` is the largest multiple
    of one ``(8, 128)`` f32 tile whose two pipeline buffers fit the
    remainder, rounded **down** and floored at one lane row (same discipline
    as :func:`choose_block_rows`; the two floors are the only sanctioned
    excess, for sub-minimal budgets), then clipped to the accumulator size.
    Parts are key-aligned ranges, which is what lets the canonical sort
    double as the partition sort (``sparse.plan_and_partition``). Explicit
    ``chunk``/``part_elems`` overrides are taken as-is.
    """
    from repro.kernels import partition as _part

    mn = m * n
    if chunk is None:
        chunk = min(_spa.DEFAULT_CHUNK, _next_pow2(max(cap, 8)))
        while chunk > 8 and 2 * chunk * 8 > vmem_budget_bytes // 2:
            chunk //= 2  # input double-buffers get at most half the budget
    if part_elems is None:
        input_bytes = 2 * chunk * 8  # double-buffered int32 keys + f32 vals
        budget_elems = max(1, (vmem_budget_bytes - input_bytes) // 8)
        step = 8 * _part.LANE_MULT if budget_elems >= 8 * _part.LANE_MULT \
            else _part.LANE_MULT
        part_elems = max(_part.LANE_MULT, _round_down(budget_elems, step))
        part_elems = min(part_elems, _round_up(mn, _part.LANE_MULT))
    parts = max(1, (mn + part_elems - 1) // part_elems)
    cap_pad = _round_up(max(cap, 1), chunk)
    num_chunks = cap_pad // chunk
    # launch-geometry telemetry (host-side, trace/launch boundary only):
    # last geometry chosen + how many times geometry was computed
    obs.counter("kernels.partition.geometry_calls").inc()
    obs.gauge("kernels.partition.parts").set(parts)
    obs.gauge("kernels.partition.part_elems").set(part_elems)
    obs.gauge("kernels.partition.chunk").set(chunk)
    obs.gauge("kernels.partition.num_chunks").set(num_chunks)
    return PartitionGeometry(part_elems=part_elems, parts=parts, chunk=chunk,
                             num_chunks=num_chunks,
                             max_steps=num_chunks + parts)


@functools.partial(jax.jit, static_argnames=("m", "n", "part_elems", "parts",
                                             "chunk", "fold"))
def partitioned_accumulate(keys_sorted: jax.Array, vals_sorted: jax.Array,
                           chunk_id: jax.Array, part_id: jax.Array, *,
                           m: int, n: int, part_elems: int, parts: int,
                           chunk: int, fold: str = "serial") -> jax.Array:
    """One-pass partitioned accumulate -> the key-ordered accumulator as
    rows: ``acc[..., key // width, key % width]`` is the accumulated value
    of ``key`` (read it with :func:`take_keys`).

    Unlike :func:`vec_accumulate_flat` this wrapper does **not** sort: it
    takes the canonically sorted, sentinel-padded stream and the step
    tables straight from ``sparse.plan_and_partition`` — the engine's one
    stable sort is shared, not repeated. Accepts ``(cap_pad,)`` streams or
    ``(B, cap_pad)`` batched stacks (with ``(B, max_steps)`` tables); the
    batch dimension becomes the leading grid dimension of one launch. The
    rows form is kept (not flattened) because flattening a batched
    accumulator is a relayout copy of the whole array on the TPU.
    """
    from repro.kernels import partition as _part

    squeeze = keys_sorted.ndim == 1
    if squeeze:
        keys_sorted = keys_sorted[None]
        vals_sorted = vals_sorted[None]
        chunk_id = chunk_id[None]
        part_id = part_id[None]
    acc = _part.partitioned_accumulate_raw(
        keys_sorted.astype(jnp.int32), vals_sorted.astype(jnp.float32),
        chunk_id, part_id, mn=m * n, part_elems=part_elems, parts=parts,
        chunk=chunk, fold=fold, interpret=interpret_kernels())
    return acc[0] if squeeze else acc


def take_keys(acc: jax.Array, keys: jax.Array) -> jax.Array:
    """Gather ``keys`` out of an accumulator: a flat ``(L,)`` array, or the
    ``(rows, width)`` form :func:`partitioned_accumulate` returns."""
    if acc.ndim == 1:
        return acc[keys]
    width = acc.shape[-1]
    return acc[keys // width, keys % width]


@functools.partial(jax.jit, static_argnames=("sent", "table_size"))
def hash_accumulate(keys: jax.Array, vals: jax.Array, *, sent: int,
                    table_size: int | None = None):
    """Faithful hash SpKAdd -> (keys[cap], vals[cap], nnz), key-compacted.

    The raw VMEM table is compacted by moving occupied slots to the front
    (stable sort on emptiness), then truncated/padded to the input capacity.
    """
    cap = keys.shape[0]
    tkeys, tvals = _hash.hash_accumulate_raw(keys, vals, sent=sent,
                                             table_size=table_size)
    occupied = tkeys != -1
    _, tk_s, tv_s = _stable_sort_pairs(jnp.logical_not(occupied), tkeys,
                                       tvals)
    occ_s = tk_s != -1
    ck = jnp.where(occ_s, tk_s, sent)[:cap]
    cv = jnp.where(occ_s, tv_s, 0.0)[:cap]
    nnz = occupied.sum().astype(jnp.int32)
    return ck.astype(jnp.int32), cv, nnz


@functools.partial(jax.jit, static_argnames=("sent", "table_size"))
def hash_symbolic(keys: jax.Array, *, sent: int,
                  table_size: int | None = None) -> jax.Array:
    """Faithful symbolic phase (distinct-key count)."""
    return _hash.hash_symbolic_raw(keys, sent=sent, table_size=table_size)


# ---------------------------------------------------------------------------
# sort-free sliding-hash launch (kernels/hash_slide.py)
# ---------------------------------------------------------------------------

class HashGeometry(_t.NamedTuple):
    """Static launch geometry of the sliding-hash grid — the single source
    of truth shared by :func:`hash_slide_tables`, the engine, and the
    probe/I-O oracle (``benchmarks/hash_accum.py``), so the oracle can
    never drift from the kernel."""

    table_size: int  # slots per part table (power of two, 8 B per slot)
    parts: int       # number of key-range parts covering m*n
    part_span: int   # key-range width owned by one part
    chunk: int       # input chunk length (power of two)
    num_chunks: int  # padded stream length / chunk


def hash_launch_geometry(cap: int, *, m: int, n: int,
                         vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
                         chunk: int | None = None) -> HashGeometry:
    """Geometry the sliding-hash launch uses for a ``cap``-long stream.

    Same budgeting discipline as :func:`partitioned_launch_geometry`: the
    double-buffered input blocks get at most half the budget (``chunk``
    halves, staying a power of two, floored at 8), then the table's two
    pipeline buffers take the remainder at 16 bytes per slot (int32 key +
    f32 value, double-buffered). If one table
    sized by ``hash_accum.hash_table_size`` for the whole stream fits,
    ``parts == 1`` and every chunk is DMA'd exactly once — the paper's
    I/O lower bound with **no pre-sort**. Otherwise the table is the
    largest fitting power of two (floored at 128 slots, the sanctioned
    excess for sub-minimal budgets), each part owns ``table_size // 2``
    keys — making the load-factor <= 0.5 bound structural — and the stream
    is re-read once per part.
    """
    mn = m * n
    if chunk is None:
        chunk = min(_spa.DEFAULT_CHUNK, _next_pow2(max(cap, 8)))
        while chunk > 8 and 2 * chunk * 8 > vmem_budget_bytes // 2:
            chunk //= 2  # input double-buffers get at most half the budget
    input_bytes = 2 * chunk * 8
    full_table = _hash.hash_table_size(min(max(cap, 1), mn))
    if 2 * full_table * 8 + input_bytes <= vmem_budget_bytes:
        table_size, part_span, parts = full_table, mn, 1
    else:
        budget_slots = max(1, (vmem_budget_bytes - input_bytes) // 16)
        table_size = max(128, _next_pow2(budget_slots + 1) // 2)
        part_span = table_size // 2
        parts = (mn + part_span - 1) // part_span
    cap_pad = _round_up(max(cap, 1), chunk)
    num_chunks = cap_pad // chunk
    obs.counter("kernels.hash_slide.geometry_calls").inc()
    obs.gauge("kernels.hash_slide.table_size").set(table_size)
    obs.gauge("kernels.hash_slide.parts").set(parts)
    obs.gauge("kernels.hash_slide.chunk").set(chunk)
    obs.gauge("kernels.hash_slide.num_chunks").set(num_chunks)
    return HashGeometry(table_size=table_size, parts=parts,
                        part_span=part_span, chunk=chunk,
                        num_chunks=num_chunks)


@functools.partial(jax.jit, static_argnames=("m", "n", "table_size",
                                             "part_span", "parts", "chunk"))
def hash_slide_tables(keys: jax.Array, vals: jax.Array, *, m: int, n: int,
                      table_size: int, part_span: int, parts: int, chunk: int):
    """Sort-free sliding-hash accumulate -> raw part tables.

    Takes ``(B, cap)`` streams in **arbitrary order** (no pre-sort — that
    is the whole point), pads to a chunk multiple with sentinels, launches
    the sliding grid, and returns ``(tkeys, tvals)`` of shape
    ``(B, parts * table_size)`` with ``tkeys == -1`` marking empty slots.
    Compaction (the single counted sort) is the caller's job.
    """
    from repro.kernels import hash_slide as _hslide

    B, cap = keys.shape
    sent = jnp.int32(m * n)
    valid = keys < m * n
    keys_c = jnp.where(valid, keys, sent).astype(jnp.int32)
    vals_c = jnp.where(valid, vals.astype(jnp.float32), 0.0)
    cap_pad = _round_up(max(cap, 1), chunk)
    keys_p = jnp.full((B, cap_pad), sent, jnp.int32).at[:, :cap].set(keys_c)
    vals_p = jnp.zeros((B, cap_pad), jnp.float32).at[:, :cap].set(vals_c)
    return _hslide.hash_slide_raw(keys_p, vals_p, mn=m * n,
                                  table_size=table_size,
                                  part_span=part_span, parts=parts,
                                  chunk=chunk,
                                  interpret=interpret_kernels())
