"""Sparse allreduce schedules over a mesh axis — SpKAdd in the collective.

The paper's three addition schedules map onto distributed reduction schedules
for top-k-sparsified gradients across P data-parallel workers:

=====================  ========================================  ==============
paper schedule          collective realization                   rounds × bytes
=====================  ========================================  ==============
k-way (hash/SPA)        ``allgather_kway``: all_gather the         1 × P·s
                        (idx, val) streams, one local k-way
                        SpKAdd (scatter-accumulate)
2-way tree              ``halving_2way``: recursive halving        lg P × ≤ P·s/2… (resparsified)
                        with 2-way sparse adds
2-way incremental       ``ring_2way``: ring fold, 2-way add        (P−1) × s·i
                        each hop (the paper's worst case)
=====================  ========================================  ==============

(s = per-worker sparse-stream bytes.) All return the *dense mean* update —
the form the optimizer applies. Dense allreduce moves 2·(P−1)/P·D bytes per
worker; the k-way sparse schedule moves P·s, a win when compression ratio
D/(P·s) > ~0.5 — exactly the regime gradient sparsification targets.

``compressed_gradient_mean`` is the DP-only pytree entry;
``compressed_gradient_mean_2d`` layers the same schedules onto a 2-D
('data', 'model') mesh — dense model-axis combine, per-shard sparse
data-axis reduction, model-axis gather (DESIGN.md §8).

Every function here runs inside ``shard_map`` over the given axis (or axis
pair).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.engine import scatter_accumulate
from repro.core.topk import SparseUpdate


# ---------------------------------------------------------------------------
# schedules (run inside shard_map; u is this worker's SparseUpdate)
# ---------------------------------------------------------------------------

def allgather_kway(u: SparseUpdate, axis: str,
                   accumulator: str = "scatter") -> jax.Array:
    """All-gather sparse streams, then one local k-way SpKAdd (paper's
    work-optimal k-way accumulation; k = axis size). The local add is the
    engine's one-touch numeric phase, since the optimizer consumes the dense
    form anyway: ``accumulator="scatter"`` is the XLA scatter the ``spa``
    regime uses; ``accumulator="vec"`` routes the same stream through the
    lane-parallel sliding fold (``kernels/vec_accum``) — bit-identical
    output (both fold per-key contributions in stream order), but the
    accumulation runs in the Pallas VMEM-tile discipline instead of a
    serial scatter."""
    idx = jax.lax.all_gather(u.idx, axis)   # (P, s)
    val = jax.lax.all_gather(u.val, axis)   # (P, s)
    p = idx.shape[0]
    flat_idx, flat_val = idx.reshape(-1), val.reshape(-1)
    if accumulator == "vec":
        from repro.kernels import ops as kops  # kernels are optional deps

        dense = kops.vec_accumulate_flat(flat_idx, flat_val, m=u.size, n=1)
    else:
        dense = scatter_accumulate(flat_idx, flat_val, u.size)
    return dense / p


def halving_2way(u: SparseUpdate, axis: str) -> jax.Array:
    """Recursive halving: lg P rounds of pairwise exchange + 2-way sparse add.

    Per round, each worker sends its (idx, val) stream to the partner at
    distance 2^r and merges — the paper's balanced-tree schedule. Streams are
    *not* re-top-k'd between rounds (lossless), so widths double each round:
    the bytes tell the tree-vs-kway story the paper's Table I tells for I/O.
    """
    p = jax.lax.axis_size(axis)
    if p & (p - 1) != 0:
        raise ValueError("halving_2way needs a power-of-two axis")
    me = jax.lax.axis_index(axis)
    idx, val = u.idx, u.val
    rounds = p.bit_length() - 1
    for r in range(rounds):
        d = 1 << r
        # pair (i, i^d) exchange: permutation is an involution
        perm = [(i, i ^ d) for i in range(p)]
        o_idx = jax.lax.ppermute(idx, axis, perm)
        o_val = jax.lax.ppermute(val, axis, perm)
        idx = jnp.concatenate([idx, o_idx])
        val = jnp.concatenate([val, o_val])
    del me
    return scatter_accumulate(idx, val, u.size) / p


def ring_2way(u: SparseUpdate, axis: str) -> jax.Array:
    """Ring fold: P−1 hops, 2-way add per hop (paper's incremental schedule).

    The accumulating stream is carried *sparse* with a growing-width buffer —
    the O(k²)-ish data movement of Alg. 1 shows up as the widening ppermute
    payloads.
    """
    p = jax.lax.axis_size(axis)
    perm = [(i, (i + 1) % p) for i in range(p)]
    idx, val = u.idx, u.val
    acc_idx, acc_val = idx, val
    for _ in range(p - 1):
        idx = jax.lax.ppermute(idx, axis, perm)
        val = jax.lax.ppermute(val, axis, perm)
        acc_idx = jnp.concatenate([acc_idx, idx])
        acc_val = jnp.concatenate([acc_val, val])
    return scatter_accumulate(acc_idx, acc_val, u.size) / p


SCHEDULES: dict[str, Callable[[SparseUpdate, str], jax.Array]] = {
    "gather_kway": allgather_kway,
    "tree_2way": halving_2way,
    "ring_2way": ring_2way,
}


def modeled_schedule_bytes(schedule: str, p: int, s: int,
                           entry_bytes: int = 8) -> int:
    """Modeled per-worker collective payload of a schedule: ``p`` workers,
    ``s``-entry streams, ``entry_bytes`` per (idx, val) pair (int32 + f32).

    ``gather_kway`` receives all P streams (P·s); ``tree_2way`` exchanges
    doubling widths over lg P rounds (s·(P−1) total); ``ring_2way`` forwards
    an s-entry payload on each of the P−1 hops. The measured twin (lowered
    HLO collective bytes) is ``benchmarks/sparse_allreduce_bytes.py``; this
    static model is what the trace span / counters can record at every
    launch without an HLO pass.
    """
    if schedule == "gather_kway":
        return p * s * entry_bytes
    return (p - 1) * s * entry_bytes  # tree_2way and ring_2way both sum to it


def sparse_allreduce(u: SparseUpdate, axis: str,
                     schedule: str = "gather_kway",
                     accumulator: str = "scatter") -> jax.Array:
    """Reduce-mean a SparseUpdate across ``axis`` (inside shard_map).

    ``accumulator`` selects the local k-way fold for the ``gather_kway``
    schedule ("scatter" | "vec"); the 2-way schedules ignore it.

    Observability: each call (once per trace — this runs inside shard_map,
    so the body is staged once for all shards) records an
    ``allreduce.sparse`` span and bumps the per-schedule call counter and
    the modeled traffic-bytes counter (:func:`modeled_schedule_bytes`).
    """
    try:
        fn = SCHEDULES[schedule]
    except KeyError:
        raise ValueError(f"unknown schedule {schedule!r}; "
                         f"choose from {sorted(SCHEDULES)}") from None
    p = jax.lax.axis_size(axis)
    s = int(u.idx.shape[0])
    nbytes = modeled_schedule_bytes(schedule, p, s)
    obs.counter(f"allreduce.calls.{schedule}").inc()
    obs.counter("allreduce.modeled_bytes").inc(nbytes)
    with obs.span("allreduce.sparse", schedule=schedule, axis=axis, p=p,
                  stream_len=s, accumulator=accumulator,
                  modeled_bytes=nbytes):
        if schedule == "gather_kway":
            return fn(u, axis, accumulator=accumulator)
        return fn(u, axis)


#: Leaves smaller than this fall back to dense psum — the sparse stream +
#: schedule overhead only pays for itself on real tensors. Overridable per
#: step via the ``min_compress_elems`` knob (tests compress tiny models).
MIN_COMPRESS_ELEMS = 16384


def _leafwise(grads, residuals, one_leaf):
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_r = treedef.flatten_up_to(residuals)
    out = [one_leaf(g, r) for g, r in zip(flat_g, flat_r)]
    mean_g = treedef.unflatten([o[0] for o in out])
    new_r = treedef.unflatten([o[1] for o in out])
    return mean_g, new_r


def compressed_gradient_mean(grads, residuals, axis: str, k_fraction: float,
                             schedule: str = "gather_kway",
                             selector: str = "block",
                             min_compress_elems: int = MIN_COMPRESS_ELEMS):
    """DP gradient reduction with the paper's technique, per pytree leaf.

    Runs INSIDE a shard_map'd train step: ``grads`` are this worker's local
    dense gradients, ``residuals`` its error-feedback state (same treedef,
    flat leaves). Returns (mean dense grads, new residuals). Leaves too small
    to be worth compressing (< ``min_compress_elems``) fall back to dense
    psum.
    """
    from repro.core.topk import global_k, sparsify_with_feedback

    def one_leaf(g, r):
        flat = g.reshape(-1)
        n = flat.shape[0]
        if n < min_compress_elems:
            return jax.lax.pmean(g, axis), r
        u, new_r = sparsify_with_feedback(flat.astype(jnp.float32), r,
                                          global_k(n, k_fraction),
                                          selector=selector)
        mean = sparse_allreduce(u, axis, schedule)
        return mean.reshape(g.shape).astype(g.dtype), new_r

    return _leafwise(grads, residuals, one_leaf)


def compressed_gradient_mean_2d(grads, residuals, data_axis: str,
                                model_axis: str, k_fraction: float,
                                schedule: str = "gather_kway",
                                selector: str = "block",
                                model_reduce: str = "reduce_scatter",
                                min_compress_elems: int = MIN_COMPRESS_ELEMS):
    """Sparse-DP × TP gradient reduction (DESIGN.md §8), per pytree leaf.

    Runs INSIDE a shard_map over a 2-D ``(data_axis, model_axis)`` mesh where
    every device holds the gradient of its own microbatch (the global batch
    is split over the flattened D×T grid; tensor-parallel-partial gradients
    look exactly the same — a per-device partial that must first be combined
    over the model axis). Per leaf, the reduction layers per-axis schedules:

    1. **model axis (dense)** — the T partials are combined densely:
       ``model_reduce="reduce_scatter"`` uses ``psum_scatter`` so each model
       shard receives only its 1/T slice of the combined gradient (the
       traffic-optimal choice); ``"psum"`` combines the full vector and
       slices locally (one fewer collective flavour — useful where
       ``psum_scatter`` lowers poorly).
    2. **data axis (sparse)** — each model shard top-k-sparsifies its slice
       against its *own* error-feedback residual (``per_shard_k`` keeps the
       global budget) and reduces it over ``data_axis`` with the chosen
       SpKAdd schedule (``gather_kway`` / ``tree_2way`` / ``ring_2way``).
    3. **model axis (gather)** — the dense per-slice means are all-gathered
       back so every device returns the full dense mean in the replicated
       layout the optimizer expects.

    ``residuals`` leaves are per-shard: flat fp32 of length
    ``ceil(leaf.size / T)`` (the padded slice this model shard owns). Leaves
    smaller than ``min_compress_elems`` fall back to a dense two-axis pmean.
    Returns (mean dense grads, new per-shard residuals).
    """
    from repro.core.topk import per_shard_k, sparsify_with_feedback

    if model_reduce not in ("reduce_scatter", "psum"):
        raise ValueError(f"unknown model_reduce {model_reduce!r}; "
                         "choose 'reduce_scatter' or 'psum'")
    t = jax.lax.axis_size(model_axis)

    def one_leaf(g, r):
        flat = g.reshape(-1)
        n = flat.shape[0]
        if n < min_compress_elems:
            return jax.lax.pmean(jax.lax.pmean(g, model_axis), data_axis), r
        shard_len = -(-n // t)
        pad = shard_len * t - n
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        if model_reduce == "reduce_scatter":
            part = jax.lax.psum_scatter(flat, model_axis,
                                        scatter_dimension=0, tiled=True)
        else:  # psum: combine full, slice locally
            full = jax.lax.psum(flat, model_axis)
            me = jax.lax.axis_index(model_axis)
            part = jax.lax.dynamic_slice(full, (me * shard_len,), (shard_len,))
        part = part / t  # mean over the model partials
        u, new_r = sparsify_with_feedback(part.astype(jnp.float32), r,
                                          per_shard_k(n, k_fraction, t),
                                          selector=selector)
        mean_shard = sparse_allreduce(u, data_axis, schedule)
        mean = jax.lax.all_gather(mean_shard, model_axis, tiled=True)
        return mean[:n].reshape(g.shape).astype(g.dtype), new_r

    return _leafwise(grads, residuals, one_leaf)
