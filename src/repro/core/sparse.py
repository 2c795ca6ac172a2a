"""Static-shape sparse containers for SpKAdd on XLA.

JAX/XLA require static shapes, so sparse matrices are stored as *padded* COO:
fixed-capacity index/value arrays plus a dynamic ``nnz`` scalar. Invalid slots
carry a sentinel key and a value of exactly 0.0 — every op in this module
preserves that invariant, which is what makes segment-sum-based compaction
safe (padding contributes nothing wherever it lands).

Keys are linearized in CSC order (``key = col * m + row``) to match the
paper's column-major traversal; a sorted PaddedCOO is therefore sorted the way
the paper's ColAdd expects its inputs.

A key is one int32 while ``m*n`` and its sentinel fit one (``m*n < 2**31``).
A wider key space keeps the same CSC order in two int32 words,
:class:`WideKeys` ``(col, row)``, compared column first; the layout follows
from the static shape (:func:`is_wide`), and no int64 enters the program.
"""
from __future__ import annotations

import functools
import operator
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as _metrics


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (>= 1). Shared by capacity bucketing
    (engine) and chunk sizing (kernel wrappers) so their roundings can
    never drift apart."""
    p = 1
    while p < x:
        p *= 2
    return p


#: Trace-time counter of stable key sorts issued through
#: :func:`stable_argsort`, :func:`stable_sort` and
#: :func:`stable_sort_pairs`, on the obs metrics registry (it survives
#: ``obs.metrics.reset()`` — the handle stays registered). Observability
#: for the engine's single-sort discipline: the one-pass partitioned
#: regimes promise exactly one stable sort per ``spkadd_auto`` call (the
#: canonical plan's, shared with the stream partition), and tests assert
#: the delta across a call.
SORT_COUNTER_NAME = "sparse.stable_argsort.calls"
_SORT_COUNTER = _metrics.counter(SORT_COUNTER_NAME)

#: Trace-time counter of the sorts among those that carry their values
#: with the keys (:func:`stable_sort_pairs`), so that no permutation is
#: gathered after them: one per canonical plan in every engine regime.
PAYLOAD_SORT_COUNTER_NAME = "sparse.payload_sorts"
_PAYLOAD_SORT_COUNTER = _metrics.counter(PAYLOAD_SORT_COUNTER_NAME)


#: Trace-time counter of the sorts among those over two-word keys
#: (:class:`WideKeys`): one multi-key sort per wide canonical plan.
WIDE_SORT_COUNTER_NAME = "sparse.wide_sorts"
_WIDE_SORT_COUNTER = _metrics.counter(WIDE_SORT_COUNTER_NAME)

#: The smallest ``m*n`` whose keys are wide: from here on neither
#: ``col*m + row`` nor the sentinel ``m*n`` fits an int32.
WIDE_KEY_SPACE = 1 << 31


def is_wide(shape: Tuple[int, int]) -> bool:
    """Whether ``shape``'s keys take two words (``m*n >= 2**31``)."""
    m, n = shape
    return m * n >= WIDE_KEY_SPACE


def require_narrow(shape: Tuple[int, int], what: str) -> None:
    """Refuse a wide key space in a path that keeps int32 keys."""
    if is_wide(shape):
        m, n = shape
        raise ValueError(
            f"{what} keeps int32 keys col*m + row, so m*n must stay below "
            f"2**31; shape {m} x {n} has m*n = {m * n}. spkadd_auto adds "
            f"such collections (sorted regime, two-word keys)")


class WideKeys(NamedTuple):
    """CSC keys of a wide shape as two int32 words, ordered ``(col, row)``:
    the same order as ``col*m + row``. Padding is ``(n, 0)``, the
    decomposition of the sentinel ``m*n``. A pytree, so a PaddedCOO that
    holds it jits and vmaps as one with int32 keys."""

    col: jax.Array
    row: jax.Array

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.col.shape


def sort_calls() -> int:
    """Number of counted stable sorts issued so far (trace-time).
    Back-compat alias for ``obs.counter("sparse.stable_argsort.calls")``."""
    return _SORT_COUNTER.value


def stable_argsort(keys: jax.Array, axis: int = -1) -> jax.Array:
    """Counted stable argsort, for callers that need the permutation itself.

    Routing every sort through this module keeps the sort-count observable
    (:func:`sort_calls`): the partitioned one-pass regimes must issue
    exactly one — the compress plan's — per engine call. This module is the
    single sanctioned home for direct ``jnp.sort``/``jnp.argsort`` calls
    (spkaddlint rule SPK101); everything else routes through here,
    :func:`stable_sort` or :func:`stable_sort_pairs`. A caller that only
    permutes arrays by the result wants :func:`stable_sort_pairs`.
    """
    _SORT_COUNTER.inc()
    return jnp.argsort(keys, axis=axis, stable=True)


def stable_sort(keys: jax.Array, axis: int = -1) -> jax.Array:
    """Counted stable *value* sort — :func:`stable_argsort`'s twin for the
    key-only consumers (symbolic phase, oracles) so every traced sort in the
    repo shows up on the same ``sparse.stable_argsort.calls`` counter."""
    _SORT_COUNTER.inc()
    return jnp.sort(keys, axis=axis, stable=True)


def stable_sort_pairs(keys: jax.Array, *payloads: jax.Array,
                      axis: int = -1) -> Tuple[jax.Array, ...]:
    """Counted stable sort of ``keys`` that carries ``payloads`` along:
    ``(keys[order], *(p[order] for p in payloads))`` for
    ``order = stable_argsort(keys)``, bit for bit, as one sort and no
    gather. An argsort is itself a sort of ``(keys, iota)``; the payloads
    take the iota's place, which saves the random gathers through the
    permutation. (On a TPU the compiler adds an iota of its own to a stable
    sort with other payloads: one more operand, far cheaper than the
    gathers.) :class:`WideKeys` sort as one multi-key sort, column word
    first."""
    _SORT_COUNTER.inc()
    _PAYLOAD_SORT_COUNTER.inc()
    if isinstance(keys, WideKeys):
        _WIDE_SORT_COUNTER.inc()
        col, row, *rest = jax.lax.sort((*keys, *payloads), dimension=axis,
                                       is_stable=True, num_keys=2)
        return (WideKeys(col, row), *rest)
    return tuple(jax.lax.sort((keys, *payloads), dimension=axis,
                              is_stable=True, num_keys=1))


def sentinel_key(shape: Tuple[int, int]):
    """Key strictly greater than any valid linearized (row, col): ``m*n``,
    or its words ``WideKeys(n, 0)`` for a wide shape."""
    m, n = shape
    return WideKeys(n, 0) if is_wide(shape) else m * n


class PaddedCOO(NamedTuple):
    """Fixed-capacity COO sparse matrix (CSC-ordered keys).

    Fields
    ------
    keys : int32[cap]   linearized ``col*m + row``; ``m*n`` marks padding
                        (a :class:`WideKeys` pair for a wide shape)
    vals : float[cap]   0.0 in padding slots (invariant)
    nnz  : int32[]      number of valid leading-or-scattered entries
    shape: (m, n)       static logical shape (not traced)
    """

    keys: jax.Array
    vals: jax.Array
    nnz: jax.Array
    shape: Tuple[int, int]

    @property
    def cap(self) -> int:
        return self.keys.shape[0]

    @property
    def rows(self) -> jax.Array:
        m, _ = self.shape
        row = self.keys.row if is_wide(self.shape) else self.keys % m
        return jnp.where(self.valid_mask(), row, m)

    @property
    def cols(self) -> jax.Array:
        m, n = self.shape
        col = self.keys.col if is_wide(self.shape) else self.keys // m
        return jnp.where(self.valid_mask(), col, n)

    def valid_mask(self) -> jax.Array:
        return _is_key(self.keys, self.shape)

    def to_dense(self) -> jax.Array:
        require_narrow(self.shape, "to_dense")
        m, n = self.shape
        flat = jnp.zeros((m * n,), dtype=self.vals.dtype)
        k = jnp.where(self.valid_mask(), self.keys, 0)
        v = jnp.where(self.valid_mask(), self.vals, 0.0)
        flat = flat.at[k].add(v)
        return flat.reshape(n, m).T  # keys are col-major


def _is_key(keys, shape: Tuple[int, int]) -> jax.Array:
    """Which slots of ``keys`` hold a key, not padding."""
    if is_wide(shape):
        return keys.col != shape[1]
    return keys != sentinel_key(shape)


def _full_keys(shape: Tuple[int, int], cap: int):
    """``cap`` padding keys of ``shape``'s layout."""
    sent = sentinel_key(shape)
    if is_wide(shape):
        return WideKeys(jnp.full((cap,), sent.col, jnp.int32),
                        jnp.zeros((cap,), jnp.int32))
    return jnp.full((cap,), sent, dtype=jnp.int32)


def _concat_keys(keys):
    """Concatenate key arrays (or :class:`WideKeys`, word by word)."""
    return jax.tree.map(lambda *ks: jnp.concatenate(ks), *keys)


def make_empty(shape: Tuple[int, int], cap: int, dtype=jnp.float32) -> PaddedCOO:
    return PaddedCOO(
        keys=_full_keys(shape, cap),
        vals=jnp.zeros((cap,), dtype=dtype),
        nnz=jnp.zeros((), dtype=jnp.int32),
        shape=shape,
    )


def from_coords(rows: jax.Array, cols: jax.Array, vals: jax.Array,
                shape: Tuple[int, int], nnz=None) -> PaddedCOO:
    """Build from (row, col, val) arrays; all entries assumed valid unless
    ``nnz`` is given, in which case trailing slots are padded out."""
    m, n = shape
    cap = rows.shape[0]
    if nnz is None:
        nnz = jnp.asarray(cap, dtype=jnp.int32)
    else:
        nnz = jnp.asarray(nnz, dtype=jnp.int32)
    idx = jnp.arange(cap)
    valid = idx < nnz
    if is_wide(shape):
        keys = WideKeys(jnp.where(valid, cols.astype(jnp.int32), n),
                        jnp.where(valid, rows.astype(jnp.int32), 0))
    else:
        keys = cols.astype(jnp.int32) * m + rows.astype(jnp.int32)
        keys = jnp.where(valid, keys, sentinel_key(shape))
    vals = jnp.where(valid, vals, 0.0)
    return PaddedCOO(keys=keys, vals=vals.astype(vals.dtype), nnz=nnz, shape=shape)


def from_dense(dense: jax.Array, cap: int) -> PaddedCOO:
    """Dense -> PaddedCOO keeping at most ``cap`` nonzeros (all, if they fit).

    Selection is by |value| via top_k so truncation (if any) keeps the heavy
    entries; with cap >= nnz(dense) this is exact.
    """
    m, n = dense.shape
    flat = dense.T.reshape(-1)  # col-major to match keys
    absv = jnp.abs(flat)
    k = min(cap, m * n)
    _, idx = jax.lax.top_k(absv, k)
    v = flat[idx]
    valid = v != 0.0
    keys = jnp.where(valid, idx.astype(jnp.int32), sentinel_key((m, n)))
    vals = jnp.where(valid, v, 0.0)
    nnz = valid.sum().astype(jnp.int32)
    # keep sorted by key for the merge-based algorithms
    keys, vals = stable_sort_pairs(keys, vals)
    out = PaddedCOO(keys=keys, vals=vals, nnz=nnz, shape=(m, n))
    if cap > k:
        out = with_capacity(out, cap)
    return out


def sort_by_key(a: PaddedCOO) -> PaddedCOO:
    keys, vals = stable_sort_pairs(a.keys, a.vals)
    return a._replace(keys=keys, vals=vals)


class CompressPlan(NamedTuple):
    """The *structural* half of :func:`compress` — everything that depends on
    keys only, plus the values carried to sorted order by the same sort.
    Factored out so the engine's SPA/blocked-SPA regimes can pair this exact
    canonical key layout (sorted distinct keys, sentinel padding, structural
    ``nnz``) with values produced by a dense accumulator instead of a
    segment-sum, and still emit bit-identical PaddedCOOs.
    """

    sorted_keys: jax.Array  # int[cap]   the input keys, stably sorted
    sorted_vals: jax.Array  # [cap]      their values, in the same order
    gid: jax.Array          # int[cap]   output group id per sorted slot
    is_new: jax.Array       # bool[cap]  first-occurrence flag per sorted slot
    out_keys: jax.Array     # int32[cap] canonical key layout (sorted + sentinel)
    nnz: jax.Array          # int32[]    structural distinct-key count
    # (the key fields are WideKeys for a wide shape)


def compress_plan(keys: jax.Array, shape: Tuple[int, int],
                  vals: jax.Array) -> CompressPlan:
    """Sort keys, flag first occurrences, and lay out the canonical output
    key array (paper Alg. 6's symbolic phase, vectorized). The one stable
    sort carries ``vals`` to ``sorted_vals``; wide keys sort as one
    multi-key sort and are new where either word changes."""
    cap = keys.shape[0]
    k_s, v_s = stable_sort_pairs(keys, vals)
    valid = _is_key(k_s, shape)
    changed = functools.reduce(operator.or_, [w[1:] != w[:-1] for w in
                                              jax.tree.leaves(k_s)])
    first = jnp.concatenate([jnp.ones((1,), bool), changed])
    is_new = first & valid
    # group id for every slot; padding inherits the last group but adds 0.0
    gid = jnp.clip(jnp.cumsum(is_new) - 1, 0, cap - 1)
    scatter_idx = jnp.where(is_new, gid, cap)  # index cap drops out of range
    out_keys = jax.tree.map(
        lambda pad, k: pad.at[scatter_idx].set(k, mode="drop"),
        _full_keys(shape, cap), k_s)
    nnz = is_new.sum().astype(jnp.int32)
    return CompressPlan(sorted_keys=k_s, sorted_vals=v_s, gid=gid,
                        is_new=is_new, out_keys=out_keys, nnz=nnz)


class PartitionSteps(NamedTuple):
    """Flattened (chunk, part) schedule of the one-pass partitioned launch.

    Step ``t`` of the sliding grid reads input chunk ``chunk_id[t]`` and
    accumulates into part ``part_id[t]`` (``part_id[t] == parts`` marks a
    padded no-op step). Both tables are non-decreasing — the stream is
    sorted and parts are contiguous key ranges — so output-part revisits
    are *consecutive* (the legal Pallas accumulation pattern) and an input
    chunk is fetched only when ``chunk_id`` changes: total input loads =
    number of distinct ``chunk_id`` runs = one per non-empty chunk.
    """

    chunk_id: jax.Array  # int32[max_steps] input chunk per grid step
    part_id: jax.Array   # int32[max_steps] output part per step; == parts -> pad


def partition_max_steps(num_chunks: int, parts: int) -> int:
    """Static step-count bound: every chunk contributes >= 1 step, each
    part transition inside a chunk and each empty part adds at most one."""
    return num_chunks + parts


def partition_steps(keys_sorted: jax.Array, *, mn: int, part_elems: int,
                    parts: int, chunk: int) -> PartitionSteps:
    """Build the (chunk, part) step schedule for a *sorted* padded stream.

    ``keys_sorted`` is ascending with sentinels (``>= mn``) at the tail and
    length a multiple of ``chunk``. Because parts are key-aligned
    (``part = key // part_elems``), each part covers a contiguous element
    range ``[lo_p, hi_p)`` found by binary search — no second sort. Empty
    parts get one step that re-reads the previous step's chunk (no extra
    load: the chunk index is unchanged) purely so their output tile is
    visited and zero-initialized; padding steps repeat the last real chunk
    with ``part_id = parts`` (masked in-kernel).
    """
    cap_pad = keys_sorted.shape[0]
    num_chunks = cap_pad // chunk
    max_steps = partition_max_steps(num_chunks, parts)
    # first sentinel position == number of valid keys; bounds clipped there
    # so a sentinel (== mn) landing inside the last part's key range when
    # mn < parts*part_elems is never scheduled as payload
    nvalid = jnp.searchsorted(keys_sorted, mn, side="left").astype(jnp.int32)
    bounds = (jnp.arange(parts + 1, dtype=jnp.int32) * part_elems)
    edges = jnp.minimum(
        jnp.searchsorted(keys_sorted, bounds, side="left").astype(jnp.int32),
        nvalid)
    lo, hi = edges[:-1], edges[1:]
    empty = hi <= lo
    first_chunk = lo // chunk
    last_chunk = jnp.where(empty, 0, jnp.maximum(hi - 1, 0) // chunk)
    prev_chunk = jnp.where(lo > 0, (lo - 1) // chunk, 0)
    nsteps = jnp.where(empty, 1, last_chunk - first_chunk + 1)
    off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(nsteps).astype(jnp.int32)])
    t = jnp.arange(max_steps, dtype=jnp.int32)
    # part of step t: last offset <= t (searchsorted right on the offsets);
    # t >= total naturally yields `parts`, the padding marker
    p_of = (jnp.searchsorted(off, t, side="right") - 1).astype(jnp.int32)
    p_clip = jnp.clip(p_of, 0, parts - 1)
    j = t - off[p_clip]
    c_of = jnp.where(empty[p_clip], prev_chunk[p_clip],
                     first_chunk[p_clip] + j)
    last_real = jnp.where(empty[parts - 1], prev_chunk[parts - 1],
                          last_chunk[parts - 1])
    pad = p_of >= parts
    return PartitionSteps(
        chunk_id=jnp.where(pad, last_real, c_of).astype(jnp.int32),
        part_id=jnp.where(pad, parts, p_of).astype(jnp.int32))


def plan_and_partition(keys: jax.Array, shape: Tuple[int, int], *,
                       vals: jax.Array, part_elems: int, chunk: int
                       ) -> Tuple[CompressPlan, jax.Array, PartitionSteps]:
    """ONE stable sort shared by the canonical plan and the stream partition.

    The partition is key-aligned (``part = key // part_elems``), so the
    composite partition key ``part * (m*n) + key`` is monotone in ``key``:
    sorting by plain key simultaneously (a) yields the canonical
    ``compress_plan`` layout and (b) groups the stream by part with keys
    sorted inside each part — the property the one-pass partitioned launch
    needs. A row-partitioned grid (``part = row // block_rows``) would
    interleave parts in key order and force a second sort to recover the
    canonical layout; aligning parts with the CSC linearization is what
    makes the single-sort discipline possible.

    Returns ``(plan, keys_sorted_padded, steps)``: the canonical plan (its
    ``sorted_vals`` are ``vals`` in plan order, carried by the same sort),
    the sorted key stream padded to a chunk multiple with sentinels, and the
    per-step partition schedule.
    """
    m, n = shape
    cap = keys.shape[0]
    plan = compress_plan(keys, shape, vals)
    cap_pad = ((max(cap, 1) + chunk - 1) // chunk) * chunk
    sent = sentinel_key(shape)
    keys_p = jnp.full((cap_pad,), sent, jnp.int32).at[:cap].set(
        plan.sorted_keys.astype(jnp.int32))
    parts = (m * n + part_elems - 1) // part_elems
    steps = partition_steps(keys_p, mn=m * n, part_elems=part_elems,
                            parts=max(parts, 1), chunk=chunk)
    return plan, keys_p, steps


def compress(a: PaddedCOO) -> PaddedCOO:
    """Combine duplicate keys (sort + segment-sum). Output is key-sorted.

    This is the static-shape analogue of the paper's output construction: the
    capacity stays ``a.cap`` (the symbolic bound), ``nnz`` becomes the exact
    count of distinct keys.
    """
    plan = compress_plan(a.keys, a.shape, a.vals)
    out_vals = jax.ops.segment_sum(plan.sorted_vals, plan.gid,
                                   num_segments=a.cap)
    # zero padding values beyond nnz (groups past nnz hold only padding sums)
    slot = jnp.arange(a.cap)
    out_vals = jnp.where(slot < plan.nnz, out_vals, 0.0)
    return PaddedCOO(keys=plan.out_keys, vals=out_vals, nnz=plan.nnz,
                     shape=a.shape)


def concat(mats, total_cap: int | None = None) -> PaddedCOO:
    """Concatenate k PaddedCOOs of identical logical shape (no dedup)."""
    shape = mats[0].shape
    for a in mats:
        if a.shape != shape:
            raise ValueError("SpKAdd inputs must share a logical shape")
    keys = _concat_keys([a.keys for a in mats])
    vals = jnp.concatenate([a.vals for a in mats])
    nnz = functools.reduce(lambda x, y: x + y, [a.nnz for a in mats])
    out = PaddedCOO(keys=keys, vals=vals, nnz=nnz, shape=shape)
    if total_cap is not None and total_cap != out.cap:
        out = with_capacity(out, total_cap)
    return out


def with_capacity(a: PaddedCOO, cap: int) -> PaddedCOO:
    """Grow (pad) or shrink (sorted-truncate) to a new capacity."""
    if cap == a.cap:
        return a
    if cap > a.cap:
        pad = cap - a.cap
        return PaddedCOO(
            keys=_concat_keys([a.keys, _full_keys(a.shape, pad)]),
            vals=jnp.concatenate([a.vals, jnp.zeros((pad,), a.vals.dtype)]),
            nnz=a.nnz,
            shape=a.shape,
        )
    s = sort_by_key(a)  # valid keys first
    return PaddedCOO(keys=jax.tree.map(lambda k: k[:cap], s.keys),
                     vals=s.vals[:cap], nnz=jnp.minimum(a.nnz, cap),
                     shape=a.shape)


def allclose(a: PaddedCOO, b: PaddedCOO, rtol=1e-5, atol=1e-6) -> bool:
    """Dense-equality check used by tests (host-side convenience)."""
    return bool(np.allclose(np.asarray(a.to_dense()), np.asarray(b.to_dense()),
                            rtol=rtol, atol=atol))


jax.tree_util.register_pytree_node(
    PaddedCOO,
    lambda a: ((a.keys, a.vals, a.nnz), a.shape),
    lambda shape, leaves: PaddedCOO(leaves[0], leaves[1], leaves[2], shape),
)
