"""The SpKAdd algorithm family (paper §II–III), adapted to XLA/TPU.

Each algorithm returns ``B = sum_i A_i`` for a list of PaddedCOO matrices of a
shared logical shape. The family mirrors the paper:

=====================  =============================================  =========
paper algorithm        this module                                    complexity
=====================  =============================================  =========
2-way incremental      ``spkadd_incremental``  (fold-left of 2-way)   O(k²·nnz·lg)
2-way tree             ``spkadd_tree``         (balanced reduction)   O(k·nnz·lg k·lg)
k-way heap             ``spkadd_sorted``       (sort + segment-sum)   O(k·nnz·lg(k·nnz))
k-way SPA              ``spkadd_spa``          (dense scatter-add)    O(k·nnz + m·n)
k-way hash             ``kernels/hash_accum``  (faithful Pallas)      O(k·nnz) expected
k-way sliding hash     ``spkadd_blocked_spa``  (VMEM-tiled Pallas)    O(k·nnz + m·n/parts per part)
k-way sliding, vec     ``spkadd_vec``          (lane-parallel Pallas) same, O(distinct) serial stores
=====================  =============================================  =========

The heap's streaming k-way merge is replaced by one vectorized sort — on TPU a
data-dependent heap serializes, while sort+segment-sum keeps all lanes busy;
both touch each input nonzero O(lg k)-ish times. The SPA/hash/sliding family
keeps the paper's one-touch-per-nonzero property.

The symbolic phase (paper Alg. 6) is :func:`symbolic_nnz` — with static shapes
it returns the exact distinct-key count used for ``nnz`` bookkeeping, while
capacity remains the a-priori bound ``sum_i cap_i``.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp

from repro.core.sparse import (PaddedCOO, compress, compress_plan, concat,
                               is_wide, require_narrow, sentinel_key,
                               stable_sort, stable_sort_pairs, with_capacity)


# ---------------------------------------------------------------------------
# symbolic phase
# ---------------------------------------------------------------------------

def symbolic_nnz(mats: Sequence[PaddedCOO]) -> jax.Array:
    """Exact nnz of the sum (distinct valid keys across all inputs).

    Paper Alg. 6 with the hash table replaced by sort+adjacent-compare; same
    O(sum nnz) data touched, vectorized. Wide keys count through the
    canonical plan, whose one sort takes both words.
    """
    if is_wide(mats[0].shape):
        cat = concat(mats)
        return compress_plan(cat.keys, cat.shape, cat.vals).nnz
    sent = sentinel_key(mats[0].shape)
    keys = stable_sort(jnp.concatenate([a.keys for a in mats]))
    valid = keys != sent
    first = jnp.concatenate([jnp.ones((1,), bool), keys[1:] != keys[:-1]])
    return (first & valid).sum().astype(jnp.int32)


def symbolic_nnz_per_column(mats: Sequence[PaddedCOO]) -> jax.Array:
    """Per-column distinct-key counts — the load-balancing signal the paper
    uses for dynamic scheduling (§III-A)."""
    shape = mats[0].shape
    m, n = shape
    sent = sentinel_key(shape)
    keys = stable_sort(jnp.concatenate([a.keys for a in mats]))
    valid = keys != sent
    first = jnp.concatenate([jnp.ones((1,), bool), keys[1:] != keys[:-1]])
    is_new = first & valid
    col = jnp.where(valid, keys // m, 0)
    return jax.ops.segment_sum(is_new.astype(jnp.int32), col, num_segments=n)


# ---------------------------------------------------------------------------
# 2-way addition (the paper's ColAdd, whole-matrix because keys linearize CSC)
# ---------------------------------------------------------------------------

def two_way_add(a: PaddedCOO, b: PaddedCOO, cap: int | None = None) -> PaddedCOO:
    """Merge-add two sparse matrices. Output capacity defaults to cap_a+cap_b,
    mirroring the worst case nnz(A+B) = nnz(A)+nnz(B)."""
    out = compress(concat([a, b]))
    if cap is not None:
        out = with_capacity(out, cap)
    return out


# ---------------------------------------------------------------------------
# k-way algorithms
# ---------------------------------------------------------------------------

def spkadd_incremental(mats: Sequence[PaddedCOO]) -> PaddedCOO:
    """Paper Alg. 1: fold-left of 2-way adds. Kept as the inefficiency
    baseline — XLA materializes every partial sum, reproducing the O(k²)
    data movement the paper measures."""
    acc = mats[0]
    for a in mats[1:]:
        acc = two_way_add(acc, a)
    return acc


def spkadd_tree(mats: Sequence[PaddedCOO]) -> PaddedCOO:
    """Paper §II-B2: balanced binary reduction of 2-way adds (lg k levels)."""
    level: List[PaddedCOO] = list(mats)
    while len(level) > 1:
        nxt: List[PaddedCOO] = []
        for i in range(0, len(level) - 1, 2):
            nxt.append(two_way_add(level[i], level[i + 1]))
        if len(level) % 2 == 1:
            nxt.append(level[-1])
        level = nxt
    return level[0]


def spkadd_sorted(mats: Sequence[PaddedCOO]) -> PaddedCOO:
    """k-way merge analogue (paper's heap, §II-C1): one global sort of all
    input nonzeros + segment-sum of duplicate keys. Touches each nonzero a
    logarithmic number of times like the heap, but with no serial dependence."""
    return compress(concat(mats))


def _resparsify_flat(flat: jax.Array, shape, out_cap: int) -> PaddedCOO:
    """Dense (m*n,) key-ordered accumulator -> key-sorted PaddedCOO keeping
    the ``out_cap`` heaviest entries (exact when the true nnz fits) — the
    shared back half of every dense-accumulator algorithm."""
    absv = jnp.abs(flat)
    _, idx = jax.lax.top_k(absv, out_cap)
    vals = flat[idx]
    valid = vals != 0.0
    keys = jnp.where(valid, idx.astype(jnp.int32), sentinel_key(shape))
    keys, vals = stable_sort_pairs(keys, jnp.where(valid, vals, 0.0))
    return PaddedCOO(keys=keys, vals=vals,
                     nnz=valid.sum().astype(jnp.int32), shape=shape)


def spkadd_spa(mats: Sequence[PaddedCOO], out_cap: int | None = None) -> PaddedCOO:
    """k-way SPA (paper Alg. 4): dense m×n accumulator + scatter-add, then one
    re-sparsification. Work-optimal O(sum nnz) scatter, O(m·n) accumulator —
    exactly the paper's memory/work trade."""
    shape = mats[0].shape
    require_narrow(shape, "spa")
    m, n = shape
    flat = jnp.zeros((m * n,), dtype=mats[0].vals.dtype)
    for a in mats:
        k = jnp.where(a.valid_mask(), a.keys, 0)
        v = jnp.where(a.valid_mask(), a.vals, 0.0)
        flat = flat.at[k].add(v)
    if out_cap is None:
        out_cap = sum(a.cap for a in mats)
    return _resparsify_flat(flat, shape, min(out_cap, m * n))


def spkadd_spa_dense(mats: Sequence[PaddedCOO]) -> jax.Array:
    """SPA variant that returns the dense accumulator directly — the form the
    gradient-allreduce path consumes (the update is applied densely anyway)."""
    shape = mats[0].shape
    m, n = shape
    flat = jnp.zeros((m * n,), dtype=mats[0].vals.dtype)
    for a in mats:
        k = jnp.where(a.valid_mask(), a.keys, 0)
        v = jnp.where(a.valid_mask(), a.vals, 0.0)
        flat = flat.at[k].add(v)
    return flat.reshape(n, m).T


def spkadd_blocked_spa(mats: Sequence[PaddedCOO], block_rows: int | None = None,
                       vmem_budget_bytes: int = 16 * 1024 * 1024) -> PaddedCOO:
    """Sliding-SPA: the TPU adaptation of the paper's sliding hash (Alg. 7/8).

    ``parts = ceil(m*n*bytes / vmem_budget)`` row-blocks; a Pallas kernel
    slides a dense VMEM accumulator tile down the row space while streaming
    every input nonzero once. See kernels/spa_accum.py (interpreter-only: a
    CPU reference; the engine's ``blocked_spa`` regime is the TPU path).
    This wrapper handles the PaddedCOO plumbing and re-sparsification.
    """
    from repro.kernels import ops as kops  # local import: kernels are optional deps

    shape = mats[0].shape
    require_narrow(shape, "blocked_spa")
    m, n = shape
    cat = concat(mats)
    flat = kops.spa_accumulate_flat(cat.keys, cat.vals, m=m, n=n,
                                    block_rows=block_rows,
                                    vmem_budget_bytes=vmem_budget_bytes)
    return _resparsify_flat(flat, shape, min(cat.cap, m * n))


def spkadd_vec(mats: Sequence[PaddedCOO], block_rows: int | None = None,
               vmem_budget_bytes: int = 16 * 1024 * 1024,
               fold: str = "auto") -> PaddedCOO:
    """Lane-parallel sliding SpKAdd — the vectorized production variant of
    :func:`spkadd_blocked_spa`.

    Same sliding VMEM grid, but the in-tile scatter is replaced by the
    serial fold or the one-hot MXU fold from :mod:`repro.kernels.vec_accum`
    (``fold="auto"`` picks by tile size). Interpreter-only, like
    :func:`spkadd_blocked_spa`; the engine's ``vec`` regime is the TPU path.
    """
    from repro.kernels import ops as kops

    shape = mats[0].shape
    require_narrow(shape, "vec")
    m, n = shape
    cat = concat(mats)
    flat = kops.vec_accumulate_flat(cat.keys, cat.vals, m=m, n=n,
                                    block_rows=block_rows,
                                    vmem_budget_bytes=vmem_budget_bytes,
                                    fold=fold)
    return _resparsify_flat(flat, shape, min(cat.cap, m * n))


def spkadd_hash(mats: Sequence[PaddedCOO]) -> PaddedCOO:
    """Faithful hash-table SpKAdd (paper Alg. 5/6) via the Pallas kernel.

    Correct and bit-faithful to the paper's probing scheme; an
    interpreter-only CPU reference (scalar probe loop). The engine's
    ``hash`` regime is the TPU path.
    """
    from repro.kernels import ops as kops

    shape = mats[0].shape
    require_narrow(shape, "hash")
    cat = concat(mats)
    keys, vals, nnz = kops.hash_accumulate(cat.keys, cat.vals,
                                           sent=sentinel_key(shape))
    out = PaddedCOO(keys=keys, vals=vals, nnz=nnz, shape=shape)
    from repro.core.sparse import sort_by_key
    return sort_by_key(out)


ALGORITHMS = {
    "incremental": spkadd_incremental,
    "tree": spkadd_tree,
    "sorted": spkadd_sorted,
    "spa": spkadd_spa,
    "vec": spkadd_vec,
    "blocked_spa": spkadd_blocked_spa,
    "hash": spkadd_hash,
}


def spkadd(mats: Sequence[PaddedCOO], algorithm: str = "sorted", **kw) -> PaddedCOO:
    """Front door: ``B = sum_i A_i`` with a selectable algorithm."""
    if algorithm not in ALGORITHMS:
        raise ValueError(f"unknown SpKAdd algorithm {algorithm!r}; "
                         f"choose from {sorted(ALGORITHMS)}")
    return ALGORITHMS[algorithm](mats, **kw)
