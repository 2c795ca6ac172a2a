"""Distributed SpGEMM (sparse SUMMA) with SpKAdd partial-product reduction.

Paper §IV-E / Fig. 5: C = A·B on a p_r × p_c process grid. At stage s each
process receives A's block-column s (broadcast along its grid row) and B's
block-row s (broadcast along its grid column), multiplies locally, and — the
step this paper is about — reduces the k = num_stages sparse partial products
with SpKAdd. Swapping the reduction from a 2-way/heap schedule to the k-way
accumulator is what made CombBLAS' SpGEMM 2x faster; the benchmark
(benchmarks/fig6_spgemm.py) reproduces that comparison.

JAX mapping: the process grid is the (data=p_r, model=p_c) mesh; the
broadcasts are ``all_gather`` along one mesh axis each (exactly SUMMA's
communication pattern); blocks are dense tiles carrying sparse contents
(static shapes), partials are sparsified to PaddedCOO and reduced with a
selectable SpKAdd algorithm. Each piece of a stage's device work is named
(``obs.stage``): ``summa.gather``, ``summa.multiply``, ``summa.from_dense``
and ``summa.reduce``, inside which the engine names its own.
"""
from __future__ import annotations


import jax
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.compat import shard_map
from repro.core.engine import spkadd_run as _spkadd_run
from repro.core.sparse import from_dense as _from_dense


def local_summa_stage(a_blk: jax.Array, b_blk: jax.Array) -> jax.Array:
    """Local multiply of one SUMMA stage (dense tiles, sparse contents)."""
    return a_blk @ b_blk


def spgemm_summa(a: jax.Array, b: jax.Array, mesh, *, algorithm: str = "auto",
                 partial_cap_per_stage: int | None = None):
    """C = A @ B with A sharded (data, model) and B sharded (data, model) on a
    p_r × p_c grid; partial products reduced via SpKAdd ``algorithm``.

    The reduction goes through the regime engine: the default ``"auto"``
    lets :func:`repro.core.engine.spkadd_auto` pick the winner for the
    (k = num_stages, partial density) regime — including the lane-parallel
    ``vec`` accumulator (kernels/vec_accum) once the partials outgrow the
    dense-SPA budget; explicit names (e.g. ``"vec"``, ``"blocked_spa"``)
    select a fixed family member for A/B comparisons.

    Returns the dense C (sharded like A) — callers needing sparse C can
    re-sparsify; keeping the reduction sparse is the point being measured.
    """
    p_r, p_c = mesh.devices.shape

    def worker(a_loc, b_loc):
        # SUMMA with stationary C: stages = p_c (A's block-cols = B's block-rows)
        # gather A's block-row stripe along 'model', B's block-col stripe along 'data'
        with obs.stage("summa.gather"):
            a_stripe = jax.lax.all_gather(a_loc, "model", axis=1, tiled=True)
            b_stripe = jax.lax.all_gather(b_loc, "data", axis=0, tiled=True)
        m_loc = a_loc.shape[0]
        k_glob = a_stripe.shape[1]
        n_loc = b_loc.shape[1]
        stages = p_c
        blk = k_glob // stages
        cap = partial_cap_per_stage or (m_loc * n_loc)
        partials = []
        for s in range(stages):
            with obs.stage("summa.multiply"):
                part = local_summa_stage(
                    jax.lax.dynamic_slice(a_stripe, (0, s * blk),
                                          (m_loc, blk)),
                    jax.lax.dynamic_slice(b_stripe, (s * blk, 0),
                                          (blk, n_loc)),
                )
            with obs.stage("summa.from_dense"):
                partials.append(_from_dense(part,
                                            cap=min(cap, m_loc * n_loc)))
        with obs.stage("summa.reduce"):
            return _spkadd_run(partials, algorithm=algorithm).to_dense()

    # check_vma=False: the vec/blocked_spa regimes run a pallas_call inside
    # the shard, and pallas_call has no replication rule
    f = shard_map(worker, mesh=mesh,
                  in_specs=(P("data", "model"), P("data", "model")),
                  out_specs=P("data", "model"), check_vma=False)
    return f(a, b)


def spgemm_reference(a: jax.Array, b: jax.Array) -> jax.Array:
    return a @ b
