"""Tables III & IV analogue: SpKAdd runtime by algorithm × k × d, for ER and
RMAT sparsity patterns.

The paper's tables are 48-core wall times; here the claim under test is the
*relative ordering and scaling*: k-way one-touch algorithms (spa/sorted/vec)
beat 2-way tree, which beats 2-way incremental, with the gap widening in k —
the work columns of Table I. The ``vec`` rows additionally report per-chunk
serial-store counts (the lane-parallel folds reduce them from O(chunk) to
O(distinct runs); the one-hot MXU fold to zero) — the metric DESIGN.md §4
says the serial scatter loses on.

``--smoke`` runs a tiny-shape cross-regime consistency check (every
algorithm, including the Pallas ``vec``/``blocked_spa``/``hash`` kernels,
plus the engine's canonical regimes) and exits nonzero on any mismatch —
the CI hook (scripts/ci.sh / .github/workflows/ci.yml).
"""
from __future__ import annotations

import argparse
import functools
import sys

import jax
import numpy as np

from benchmarks.common import emit, gen_collection, time_fn, write_json
from repro.core.engine import (explain_dispatch, spkadd_auto, spkadd_batched,
                               stack_collections)
from repro.core.sparse import concat
from repro.core.spkadd import spkadd

ALGOS = ["incremental", "tree", "sorted", "spa", "vec"]
KERNEL_ALGOS = ["blocked_spa", "hash"]  # slow faithful baselines, opt-in


def _store_counts(mats):
    """Serial-store counts for the concatenated stream under the vec launch
    geometry (host-side oracle; see kernels/vec_accum.chunk_store_counts)."""
    from repro.kernels import ops as kops

    cat = concat(mats)
    m, n = cat.shape
    return kops.vec_store_counts(np.asarray(cat.keys), m=m, n=n)


def run(kind: str, m=2048, n=32, ks=(4, 16, 64), ds=(4, 16, 64),
        include_kernels=False):
    rows = {}
    for k in ks:
        for d in ds:
            mats = gen_collection(kind, k, m, n, d, seed=k * 100 + d)
            algos = ALGOS + (KERNEL_ALGOS if include_kernels else [])
            for alg in algos:
                fn = jax.jit(functools.partial(spkadd, algorithm=alg))
                us = time_fn(fn, mats)
                rows[(alg, k, d)] = us
                emit(f"table_{kind}/{alg}/k={k}/d={d}", us,
                     f"nnz_in={k * d * n}")
            # the serial-store story at this cell: O(chunk) vs one-hot's 0
            sc = _store_counts(mats)
            emit(f"table_{kind}/stores/k={k}/d={d}", sc["serial"],
                 f"onehot_fold={sc['onehot_fold']}")
            # the engine's pick for this cell, timed under the same harness
            us = time_fn(jax.jit(spkadd_auto), mats)
            _, picked = explain_dispatch(mats)
            rows[("auto", k, d)] = us
            emit(f"table_{kind}/auto/k={k}/d={d}", us, f"dispatch={picked}")
    # derived: ratio of incremental to sorted at max k (the paper's headline)
    kmax, dmid = max(ks), ds[len(ds) // 2]
    if ("incremental", kmax, dmid) in rows:
        ratio = rows[("incremental", kmax, dmid)] / rows[("sorted", kmax, dmid)]
        emit(f"table_{kind}/ratio_incremental_vs_sorted_k{kmax}", ratio,
             "paper: >5x for large k")
    return rows


def run_batched(kind: str, b=8, k=8, m=2048, n=32, d=16):
    """Batched engine vs a Python loop of per-collection adds: the win is one
    XLA program (and one dispatch) for all B independent sums."""
    colls = [gen_collection(kind, k, m, n, d, seed=1000 * i + d)
             for i in range(b)]
    stacked = stack_collections(colls)

    batched = jax.jit(spkadd_batched)
    us_batched = time_fn(batched, stacked)
    emit(f"table_{kind}/batched/B={b}/k={k}/d={d}", us_batched, "one program")

    auto = jax.jit(spkadd_auto)

    def loop(colls):
        return [auto(c) for c in colls]

    us_loop = time_fn(loop, colls)
    emit(f"table_{kind}/loop/B={b}/k={k}/d={d}", us_loop, "python loop")
    emit(f"table_{kind}/batched_speedup/B={b}", us_loop / max(us_batched, 1e-9),
         "loop_us / batched_us")


def smoke(kind="er", k=6, m=64, n=8, d=4) -> int:
    """Tiny-shape cross-regime consistency gate (the CI hook).

    Every algorithm in the family — including the Pallas kernels and the
    new ``vec`` regime — must agree with the dense oracle, and every
    engine-canonical regime must be *bit-identical* to the sorted
    reference. Returns a nonzero exit code on any mismatch.
    """
    from repro.core import engine as E

    mats = gen_collection(kind, k, m, n, d, seed=7)
    ref = spkadd(mats, algorithm="sorted")
    ref_dense = np.asarray(ref.to_dense())
    failures = 0
    for alg in ALGOS + KERNEL_ALGOS:
        out = spkadd(mats, algorithm=alg)
        ok = np.allclose(np.asarray(out.to_dense()), ref_dense,
                         rtol=1e-4, atol=1e-5)
        emit(f"smoke/{alg}", 0.0 if ok else 1.0, "dense-agree" if ok else
             "MISMATCH vs sorted reference")
        failures += (not ok)
    for regime in ("tree", "sorted", "spa", "vec", "blocked_spa"):
        use = mats[:3] if regime == "tree" else mats
        want = spkadd(use, algorithm="sorted")
        got = E._CANONICAL[regime](use)
        ok = (np.array_equal(np.asarray(want.keys), np.asarray(got.keys))
              and np.array_equal(np.asarray(want.vals), np.asarray(got.vals))
              and int(want.nnz) == int(got.nnz))
        emit(f"smoke/canonical/{regime}", 0.0 if ok else 1.0,
             "bit-identical" if ok else "BIT MISMATCH vs canonical contract")
        failures += (not ok)
    sc = _store_counts(mats)
    emit("smoke/serial_stores", float(sc["serial"]), "serial fold")
    if failures:
        emit("smoke/FAILED", float(failures), "cross-regime mismatches")
    else:
        emit("smoke/ok", 0.0, "all regimes agree")
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-shape cross-regime consistency gate (CI)")
    ap.add_argument("--include-kernels", action="store_true",
                    help="also time the Pallas kernel algorithms")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write every emitted record as a BENCH_*.json "
                         "artifact (machine-readable perf trajectory)")
    args = ap.parse_args()
    if args.smoke:
        rc = smoke()
        if args.json:
            write_json(args.json, suite="table34_smoke", status=rc)
        sys.exit(rc)
    run("er", include_kernels=args.include_kernels)
    run("rmat", include_kernels=args.include_kernels)
    run_batched("er")
    if args.json:
        write_json(args.json, suite="table34")


if __name__ == "__main__":
    main()
