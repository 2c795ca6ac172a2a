"""Regime-aware SpKAdd engine: auto-dispatch + batched execution.

The paper's central empirical result (Fig. 2, Tables III/IV) is that no
single SpKAdd algorithm wins everywhere:

- **tiny k**: 2-way tree merging is competitive (few partial sums, the
  O(k) accumulator setup doesn't amortize);
- **large k / high aggregate density / high compression factor**: the
  one-touch hash/SPA family dominates (each input nonzero is touched once,
  the accumulator cost amortizes over many collisions);
- **huge accumulators**: the sliding/blocked variant keeps the SPA win by
  tiling the accumulator through fast memory (paper Alg. 7/8, VMEM here);
- **everything else**: the k-way merge (here: sort + segment-sum) is the
  robust fallback.

:func:`spkadd_auto` computes the paper's regime signals — k, aggregate
density ``sum nnz / (m·n)``, and compression factor ``cf = sum nnz /
nnz(B)`` — and picks the region's winner from a calibratable cost-model
table (see DESIGN.md §Engine for the region table;
``benchmarks/fig2_regions.py --dump-cost-model`` re-measures the boundaries
on the current hardware and dumps a table this module can load).

**Canonical output contract.** Every engine path returns the *same*
PaddedCOO bit-for-bit: capacity ``sum_i cap_i``, keys sorted with sentinel
padding, structural ``nnz`` (value-cancelled keys are kept, as in the
paper's symbolic/numeric split), and values accumulated in input-stream
order. This works because the structural layout is computed once by
:func:`repro.core.sparse.compress_plan` for every regime, and each regime
only changes *how the per-key value sums are produced*: segment-sum over the
sorted stream (merge regime), a dense scatter accumulator (SPA regime),
the VMEM-tiled Pallas accumulator (blocked regime), or the lane-parallel
vectorized folds (vec regime, ``kernels/vec_accum``) — all of which fold
each key's contributions in the same stream order. Downstream callers can
therefore swap regimes freely without perturbing checkpoints or tests.

**Shared-sort contract (one-pass partitioned regimes).** The ``vec`` and
``blocked_spa`` regimes run the stream-partitioned sliding accumulator
(:mod:`repro.kernels.partition`): the canonical plan's stable sort is the
*only* sort on the path — its order doubles as the partition order
because parts are key-aligned ranges (``sparse.plan_and_partition``), the
kernel wrappers take the pre-sorted stream and never re-sort, and each
input chunk is read exactly once (the paper's I/O lower bound, vs the
legacy grid's ``parts × N``). ``sparse.sort_calls()`` counts the stable
sorts; tests pin the count at one per engine call. Every regime's one
sort carries the values as its payload (``sparse.stable_sort_pairs``), so
no permutation is gathered after it.

**Sort-free hash regime.** The ``hash`` regime (the paper's Tables 3/4
winner) goes further: the *unsorted* stream is accumulated directly into
per-part VMEM hash tables (:mod:`repro.kernels.hash_slide`) and the single
counted sort happens *after* accumulation, compacting the tables to the
canonical layout — zero sorts before compaction (gauge
``engine.hash.presort_sorts``), one sort total. It wins where sorting is
wasted work: low compression factor, table fits fast memory (DESIGN.md
§4.4).

:func:`spkadd_batched` adds a *stack* of B collections (shared logical
shape and capacities, independent sums) in one XLA program instead of a
Python loop: pure-jnp regimes are vmapped, while a ``vec``/``blocked_spa``
selection runs the batched partitioned Pallas launch (leading batch grid
dimension, per-batch step tables) — no silent downgrade to the dense
scatter; :func:`explain_batched_dispatch` reports the requested and
effective algorithm.
"""
from __future__ import annotations

import functools
import json
import logging
import math
import os
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.sparse import (WIDE_KEY_SPACE, CompressPlan, PaddedCOO,
                               compress_plan, concat, is_wide, next_pow2,
                               plan_and_partition, require_narrow,
                               sentinel_key, sort_calls, stable_sort_pairs,
                               with_capacity)
from repro.core import spkadd as _alg
from repro.kernels import VMEM_BUDGET_BYTES

_log = logging.getLogger("repro.engine")


# ---------------------------------------------------------------------------
# regime signals (paper Fig. 2 axes)
# ---------------------------------------------------------------------------

class RegimeSignals(NamedTuple):
    """The paper's dispatch axes, static at trace time.

    ``density`` and ``compression`` are *capacity-based estimates* by default
    (capacities are the a-priori nnz bounds and the only static information
    under jit); :func:`regime_signals` can compute exact values from concrete
    inputs when available.
    """

    k: int               # number of input matrices
    density: float       # aggregate input density: sum nnz / (m*n)
    compression: float   # cf = sum nnz / nnz(B)  (>= 1)
    accum_elems: int     # dense accumulator size m*n (SPA feasibility)


def estimate_compression(total_nnz: float, mn: int) -> float:
    """Expected cf for uniformly random keys (ER model): distinct keys
    ``≈ mn·(1 − (1 − 1/mn)^N)``, the standard occupancy estimate."""
    if total_nnz <= 0 or mn <= 0:
        return 1.0
    distinct = mn * -math.expm1(total_nnz * math.log1p(-1.0 / mn)) \
        if mn > 1 else 1.0
    return max(1.0, total_nnz / max(distinct, 1.0))


def regime_signals(mats: Sequence[PaddedCOO],
                   exact: bool = False) -> RegimeSignals:
    """Compute the dispatch signals for a collection.

    ``exact=True`` reads concrete ``nnz`` and runs the symbolic phase — only
    valid outside jit (concrete inputs); the default uses capacities, which
    keeps :func:`spkadd_auto` fully traceable.
    """
    m, n = mats[0].shape
    mn = m * n
    k = len(mats)
    if exact:
        total = float(sum(int(a.nnz) for a in mats))
        out_nnz = float(int(_alg.symbolic_nnz(mats)))
        cf = total / max(out_nnz, 1.0)
    else:
        total = float(sum(a.cap for a in mats))
        cf = estimate_compression(total, mn)
    return RegimeSignals(k=k, density=total / max(mn, 1), compression=cf,
                         accum_elems=mn)


# ---------------------------------------------------------------------------
# cost model (Fig. 2 region boundaries; calibratable)
# ---------------------------------------------------------------------------

#: Region boundaries of the dispatch table. Values are the defaults measured
#: on the interpret-mode CPU backend; ``benchmarks/fig2_regions.py`` can
#: re-measure and dump a table for the current hardware. These in-code
#: values are the fallback of last resort — :func:`default_cost_model`
#: overlays the checked-in ``configs/cost_model_default.json`` and then the
#: ``SPKADD_COST_MODEL`` env var, so calibrated tables drop in without code
#: edits.
DEFAULT_COST_MODEL: Dict[str, float] = {
    # tree merging only wins for tiny k (Fig. 2 bottom band). Also the k
    # range where the balanced tree degenerates to a left fold, which is what
    # keeps the canonical-output contract exact.
    "tree_max_k": 3,
    # dense-SPA regime: the accumulator must fit the fast-memory budget and
    # the scatter must amortize it (aggregate density or compression high).
    "spa_max_accum_elems": float(1 << 22),   # 16 MiB of f32 accumulator
    "spa_min_density": 1.0 / 64.0,
    "spa_min_compression": 1.25,
    # vec regime: the lane-parallel sliding accumulator (kernels/vec_accum) —
    # the production pick for accumulators past the dense-SPA budget. Tiles
    # at or below vec_onehot_max_block_elems use the one-hot MXU fold
    # (zero serial stores); larger tiles use the serial fold (one tile-row
    # store per element).
    "vec_max_accum_elems": float(1 << 26),
    "vec_min_density": 1.0 / 32.0,
    "vec_onehot_max_block_elems": 4096.0,
    # sliding/blocked-SPA regime: the serial-scatter fallback for the same
    # accumulator range, reachable when a calibrated table disables vec
    # (vec_max_accum_elems = 0) or prices it out on density.
    "blocked_spa_max_accum_elems": float(1 << 26),
    "blocked_spa_min_density": 1.0 / 16.0,
    # sort-free sliding-hash regime (paper Tables 3/4, the title's winner):
    # pays zero sorts before compaction, so it beats the sort-paying family
    # exactly where sorting is wasted — low compression factor (few
    # duplicates to merge) — provided the stream is big enough for the
    # table setup to amortize and the pow2 table at load factor <= 0.5
    # (2 * next_pow2-of-distinct-bound slots) fits fast memory.
    "hash_min_total_nnz": 512.0,
    "hash_max_compression": 1.5,
    "hash_max_table_elems": float(1 << 21),
}

#: Env var naming a JSON cost-model file (as written by
#: ``benchmarks/fig2_regions.py --dump-cost-model``) that overrides the
#: checked-in defaults for every dispatch in the process.
COST_MODEL_ENV = "SPKADD_COST_MODEL"

#: The checked-in default table (same package as the model configs).
COST_MODEL_CONFIG_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "configs", "cost_model_default.json")


@functools.lru_cache(maxsize=None)
def _cost_model_from(path: str) -> Dict[str, float]:
    with open(path) as f:
        return {str(k): float(v) for k, v in json.load(f).items()}


def default_cost_model() -> Dict[str, float]:
    """The process-wide dispatch table: in-code defaults, overlaid with the
    checked-in ``configs/cost_model_default.json``, overlaid with the file
    named by ``$SPKADD_COST_MODEL`` (if set). Files are parsed once per path
    (cached); a missing env-var path raises rather than silently falling
    back — a calibrated table that doesn't load should not go unnoticed.
    """
    cm = dict(DEFAULT_COST_MODEL)
    if os.path.exists(COST_MODEL_CONFIG_PATH):
        cm.update(_cost_model_from(COST_MODEL_CONFIG_PATH))
    env_path = os.environ.get(COST_MODEL_ENV)
    if env_path:
        cm.update(_cost_model_from(env_path))
    return cm


def select_algorithm(signals: RegimeSignals,
                     cost_model: Optional[Dict[str, float]] = None) -> str:
    """Map regime signals to the Fig. 2 region winner.

    A key space of ``2**31`` or more (two-word keys, ``sparse.WideKeys``)
    goes to ``sorted`` whatever the cost model says: the one regime whose
    single sort takes both words. Every other regime keeps int32 keys, and
    ``tree`` would sort once per 2-way add."""
    if signals.accum_elems >= WIDE_KEY_SPACE:
        return "sorted"
    cm = default_cost_model()
    if cost_model:
        cm.update(cost_model)
    if signals.k <= cm["tree_max_k"]:
        return "tree"
    spa_worthwhile = (signals.density >= cm["spa_min_density"]
                      or signals.compression >= cm["spa_min_compression"])
    if signals.accum_elems <= cm["spa_max_accum_elems"] and spa_worthwhile:
        return "spa"
    total = signals.density * signals.accum_elems
    table_elems = next_pow2(2 * max(int(min(total, signals.accum_elems)), 1))
    if (total >= cm["hash_min_total_nnz"]
            and signals.compression <= cm["hash_max_compression"]
            and table_elems <= cm["hash_max_table_elems"]):
        return "hash"
    if (signals.accum_elems <= cm["vec_max_accum_elems"]
            and signals.density >= cm["vec_min_density"]):
        return "vec"
    if (signals.accum_elems <= cm["blocked_spa_max_accum_elems"]
            and signals.density >= cm["blocked_spa_min_density"]):
        return "blocked_spa"
    return "sorted"


def calibrate_cost_model(cells) -> Dict[str, float]:
    """Fit region boundaries from measured per-cell winners.

    ``cells`` is an iterable of ``((k, aggregate_density), winner)`` pairs
    (or ``((k, aggregate_density, compression), winner)`` triples, or an
    equivalent dict) as produced by ``benchmarks/fig2_regions.py``.
    Pairs, not a dict keyed on (k, density): the same cell measured on
    different sparsity patterns (ER vs RMAT) must contribute *both*
    winners, not have one silently overwrite the other. Boundaries not
    identifiable from the sample keep their defaults.
    """
    items = list(cells.items()) if hasattr(cells, "items") else list(cells)
    cm = dict(DEFAULT_COST_MODEL)
    tree_ks = [key[0] for key, alg in items if alg == "tree"]
    if tree_ks:
        cm["tree_max_k"] = max(tree_ks)
    spa_ds = [key[1] for key, alg in items if alg in ("spa", "blocked_spa")]
    if spa_ds:
        cm["spa_min_density"] = min(spa_ds)
        cm["blocked_spa_min_density"] = min(spa_ds)
    vec_ds = [key[1] for key, alg in items if alg == "vec"]
    if vec_ds:
        cm["vec_min_density"] = min(vec_ds)
    # hash vs vec is a compression-factor boundary, so hash cells carry cf
    # as an optional third axis: ((k, density, cf), winner).
    hash_cfs = [key[2] for key, alg in items if alg == "hash" and len(key) > 2]
    if hash_cfs:
        cm["hash_max_compression"] = max(hash_cfs)
    return cm


def dump_cost_model(cm: Dict[str, float], path: str) -> None:
    with open(path, "w") as f:
        json.dump(cm, f, indent=2, sort_keys=True)
        f.write("\n")


def load_cost_model(path: str) -> Dict[str, float]:
    with open(path) as f:
        loaded = json.load(f)
    cm = dict(DEFAULT_COST_MODEL)
    cm.update(loaded)
    return cm


# ---------------------------------------------------------------------------
# canonical execution paths
# ---------------------------------------------------------------------------

def scatter_accumulate(keys: jax.Array, vals: jax.Array,
                       length: int) -> jax.Array:
    """Dense SPA numeric phase: fold a (key, val) stream into a flat
    accumulator of ``length`` slots. Keys outside ``[0, length)``
    (sentinels) land in a discard slot. Duplicate keys add in stream order
    only where the backend's scatter does so: on a TPU, pass key-sorted
    streams (``_run_spa``).

    This is the one scatter every dense consumer shares — the engine's SPA
    regime, the sparse-allreduce k-way schedule, and ``to_dense`` semantics.
    """
    safe = jnp.clip(keys, 0, length)
    acc = jnp.zeros((length + 1,), vals.dtype).at[safe].add(vals)
    return acc[:length]


def _canonical_gather(out_keys: jax.Array, nnz: jax.Array, acc: jax.Array,
                      sent: int, dtype) -> jax.Array:
    """The canonical value gather every dense-accumulator regime shares —
    single-collection and batched (vmapped) paths must use this one
    function so their sentinel/nnz/dtype conventions can never diverge.
    ``acc`` is a flat key-ordered accumulator or the partitioned launch's
    ``(rows, width)`` form (``kernels.ops.take_keys``)."""
    from repro.kernels.ops import take_keys

    gather_keys = jnp.where(out_keys != sent, out_keys, 0)
    return jnp.where(jnp.arange(out_keys.shape[0]) < nnz,
                     take_keys(acc, gather_keys), 0.0).astype(dtype)


def _canonical_from_plan(cat: PaddedCOO, plan: CompressPlan,
                         flat: jax.Array) -> PaddedCOO:
    """Pair a precomputed canonical plan with per-key values gathered from a
    dense accumulator ``flat`` (col-major, ``flat[key]``)."""
    out_vals = _canonical_gather(plan.out_keys, plan.nnz, flat,
                                 sentinel_key(cat.shape), cat.vals.dtype)
    return PaddedCOO(keys=plan.out_keys, vals=out_vals, nnz=plan.nnz,
                     shape=cat.shape)


def _run_spa(mats: Sequence[PaddedCOO],
             cost_model: Optional[Dict[str, float]] = None) -> PaddedCOO:
    """SPA regime: one-touch dense scatter for the numeric phase, canonical
    structural layout for the output. The scatter takes the stream in the
    plan's stable key order: XLA leaves the order in which a scatter
    combines duplicate indices unspecified, and on a TPU an unsorted
    scatter does not add them in stream order, while a key-sorted one folds
    them as ``segment_sum`` does in the ``sorted`` regime."""
    require_narrow(mats[0].shape, "the spa regime")
    with obs.stage("spkadd.plan"):
        cat = concat(mats)
        m, n = cat.shape
        plan = compress_plan(cat.keys, cat.shape, cat.vals)
    with obs.stage("spkadd.accumulate"):
        flat = scatter_accumulate(plan.sorted_keys, plan.sorted_vals, m * n)
    with obs.stage("spkadd.output"):
        return _canonical_from_plan(cat, plan, flat)


def _partition_fold(regime: str, geom, vmem_budget_bytes: int,
                    cost_model: Optional[Dict[str, float]]) -> str:
    """In-tile fold for a partitioned launch: ``blocked_spa`` keeps the
    serial fidelity scatter; ``vec`` picks one-hot vs serial on the cost
    model's tile-size boundary (one-hot additionally requires its whole
    step working set — tile, double-buffered inputs, and the
    ``(chunk × part_elems)`` intermediates — to fit the VMEM budget; see
    ``kernels.ops.fold_working_set_bytes``)."""
    from repro.kernels import ops as kops

    if regime == "blocked_spa":
        return "serial"
    cm = default_cost_model()
    if cost_model:
        cm.update(cost_model)
    onehot_ws = kops.fold_working_set_bytes(
        "onehot", tile_elems=geom.part_elems, chunk=geom.chunk)
    return "onehot" if (geom.part_elems <= cm["vec_onehot_max_block_elems"]
                        and onehot_ws <= vmem_budget_bytes) else "serial"


def _partitioned_core(keys: jax.Array, vals: jax.Array,
                      shape: Tuple[int, int], regime: str,
                      vmem_budget_bytes: int,
                      cost_model: Optional[Dict[str, float]]) -> PaddedCOO:
    """The ONE partitioned pipeline — plan/sort, step tables, Pallas launch,
    canonical gather — over ``(B, cap)`` concatenated streams. Both the
    single-collection regimes (B = 1) and :func:`spkadd_batched` run this
    exact function, so the two paths cannot drift apart and the
    bit-identity contract between them is structural, not tested-for."""
    from repro.kernels import ops as kops  # kernels are optional deps

    require_narrow(shape, f"the {regime} regime")
    m, n = shape
    cap = keys.shape[-1]
    geom = kops.partitioned_launch_geometry(
        cap, m=m, n=n, vmem_budget_bytes=vmem_budget_bytes)
    fold = _partition_fold(regime, geom, vmem_budget_bytes, cost_model)
    obs.counter("engine.partitioned.launches").inc()
    obs.counter(f"engine.partitioned.fold.{fold}").inc()
    with obs.stage("spkadd.plan"):
        plan, keys_p, steps = jax.vmap(functools.partial(
            plan_and_partition, shape=shape, part_elems=geom.part_elems,
            chunk=geom.chunk))(keys, vals=vals)
        vals_p = jnp.zeros(keys_p.shape, jnp.float32).at[:, :cap].set(
            plan.sorted_vals.astype(jnp.float32))
    with obs.stage("spkadd.accumulate"):
        acc = kops.partitioned_accumulate(
            keys_p, vals_p, steps.chunk_id, steps.part_id, m=m, n=n,
            part_elems=geom.part_elems, parts=geom.parts, chunk=geom.chunk,
            fold=fold)

    sent = sentinel_key(shape)
    with obs.stage("spkadd.output"):
        out_vals = jax.vmap(
            lambda ok, p_nnz, b_acc: _canonical_gather(ok, p_nnz, b_acc,
                                                       sent, vals.dtype)
        )(plan.out_keys, plan.nnz, acc)
    return PaddedCOO(keys=plan.out_keys, vals=out_vals, nnz=plan.nnz,
                     shape=shape)


def _unbatch(out: PaddedCOO, keys_stage: str) -> PaddedCOO:
    """The single result of a B = 1 run of a shared core. The keys and
    ``nnz`` are taken in ``keys_stage``, the stage that made them, so that
    an op XLA fuses under their squeeze keeps its own stage."""
    with obs.stage(keys_stage):
        keys, nnz = out.keys[0], out.nnz[0]
    with obs.stage("spkadd.output"):
        vals = out.vals[0]
    return PaddedCOO(keys=keys, vals=vals, nnz=nnz, shape=out.shape)


def _run_partitioned(mats: Sequence[PaddedCOO], regime: str,
                     vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
                     cost_model: Optional[Dict[str, float]] = None
                     ) -> PaddedCOO:
    """One-pass partitioned regimes (``vec`` / ``blocked_spa``): one stable
    sort (the canonical plan's, shared with the stream partition — see the
    module docstring), then the I/O-optimal Pallas launch reads each input
    chunk exactly once and the canonical gather reuses the same plan.
    Runs the shared core as a B = 1 batch."""
    with obs.stage("spkadd.plan"):
        cat = concat(mats)
    # the keys and nnz of the partitioned core are the plan's
    return _unbatch(_partitioned_core(cat.keys[None], cat.vals[None],
                                      cat.shape, regime, vmem_budget_bytes,
                                      cost_model), "spkadd.plan")


def _run_blocked_spa(mats: Sequence[PaddedCOO],
                     cost_model: Optional[Dict[str, float]] = None,
                     **kw) -> PaddedCOO:
    """Sliding-SPA regime: the partitioned one-pass launch with the serial
    fidelity fold; output layout is canonical."""
    return _run_partitioned(mats, "blocked_spa", cost_model=cost_model, **kw)


def _run_vec(mats: Sequence[PaddedCOO],
             cost_model: Optional[Dict[str, float]] = None,
             **kw) -> PaddedCOO:
    """Vec regime: the partitioned one-pass launch with the lane-parallel
    folds (``kernels/vec_accum``); per-key sums are bit-identical to every
    other regime (DESIGN.md §3.3/§4) because the stream is in canonical
    plan order."""
    return _run_partitioned(mats, "vec", cost_model=cost_model, **kw)


def _hash_core(keys: jax.Array, vals: jax.Array, shape: Tuple[int, int],
               vmem_budget_bytes: int,
               cost_model: Optional[Dict[str, float]]) -> PaddedCOO:
    """The ONE sort-free sliding-hash pipeline over ``(B, cap)`` streams.

    Unlike every other regime there is **no sort before accumulation**: the
    unsorted concatenated stream goes straight into the sliding-hash Pallas
    launch (``kernels/hash_slide``), which inserts-or-accumulates each
    nonzero into per-part VMEM tables in stream order. Because slot values
    start at f32 zero and duplicates add on top in stream order, the
    per-key value is exactly the canonical left fold — so compacting the
    tables (occupied slots sorted by key, sentinel padding, structural
    ``nnz``) reproduces the canonical PaddedCOO bit-for-bit. That
    compaction's ``stable_sort_pairs`` is the single counted sort of a hash
    dispatch; the ``engine.hash.presort_sorts`` gauge (pinned at zero)
    certifies nothing sorted before the tables were built. Shared by the
    single-collection regime (B = 1) and :func:`spkadd_batched`.
    """
    from repro.kernels import ops as kops  # kernels are optional deps

    require_narrow(shape, "the hash regime")
    m, n = shape
    B, cap = keys.shape
    sent = sentinel_key(shape)
    geom = kops.hash_launch_geometry(
        cap, m=m, n=n, vmem_budget_bytes=vmem_budget_bytes)
    obs.counter("engine.hash.launches").inc()
    sorts_before = sort_calls()
    with obs.stage("spkadd.accumulate"):
        tkeys, tvals = kops.hash_slide_tables(
            keys, vals, m=m, n=n, table_size=geom.table_size,
            part_span=geom.part_span, parts=geom.parts, chunk=geom.chunk)
    # the zero-presort pin: tables were built without any canonical sort
    obs.gauge("engine.hash.presort_sorts").set(sort_calls() - sorts_before)

    # compaction — the ONE stable sort of a hash dispatch. Part tables are
    # key-range ordered, so a single batched sort of (key, value) over the
    # concatenated tables yields canonical order; the stable tie-break keeps
    # sentinel (empty) slots behind every real key.
    obs.counter("engine.hash.compaction_sorts").inc()
    with obs.stage("spkadd.output"):
        occupied = tkeys != -1
        ck = jnp.where(occupied, tkeys, sent)
        ck_s, cv_s = stable_sort_pairs(ck, tvals)
        tab = ck.shape[-1]
        if tab >= cap:
            out_keys = ck_s[:, :cap]
            out_f32 = cv_s[:, :cap]
        else:
            out_keys = jnp.concatenate(
                [ck_s, jnp.full((B, cap - tab), sent, jnp.int32)], axis=-1)
            out_f32 = jnp.concatenate(
                [cv_s, jnp.zeros((B, cap - tab), jnp.float32)], axis=-1)
        nnz = occupied.sum(axis=-1).astype(jnp.int32)
        out_vals = jnp.where(out_keys != sent, out_f32,
                             0.0).astype(vals.dtype)
    return PaddedCOO(keys=out_keys, vals=out_vals, nnz=nnz, shape=shape)


def _run_hash(mats: Sequence[PaddedCOO],
              cost_model: Optional[Dict[str, float]] = None,
              vmem_budget_bytes: int = VMEM_BUDGET_BYTES) -> PaddedCOO:
    """Sort-free sliding-hash regime: zero sorts before compaction, one
    stable sort total; output layout is canonical. Runs the shared core as
    a B = 1 batch."""
    with obs.stage("spkadd.plan"):
        cat = concat(mats)
    return _unbatch(_hash_core(cat.keys[None], cat.vals[None], cat.shape,
                               vmem_budget_bytes, cost_model), "spkadd.output")


def _run_tree(mats: Sequence[PaddedCOO],
              cost_model: Optional[Dict[str, float]] = None) -> PaddedCOO:
    """Tiny-k regime, canonical-contract-preserving for *any* tree_max_k:

    - k=1: ``spkadd_tree`` would return the input uncompressed (no final
      2-way add), leaking duplicate keys — route through the compress.
    - k<=3: the balanced tree is a left fold; use it as-is.
    - k>3 (reachable only via a calibrated/custom ``tree_max_k``): the
      balanced tree sums pairs as (a+b)+(c+d), not in stream order, so it
      would break bit-identity — fold left instead (the incremental
      schedule), which sums every key in stream order. The fold runs as a
      loop over a fixed-capacity accumulator (capacity ``sum_i cap_i``,
      which always holds the distinct keys so far), so XLA compiles one
      2-way add instead of k-1 differently-shaped ones (at k = 64 and
      4M inputs, minutes of TPU compile). O(k·sum cap) data movement is
      acceptable exactly because this regime only wins at tiny k.
    """
    if len(mats) == 1:
        return _alg.spkadd_sorted(mats)
    if len(mats) <= 3:
        return _alg.spkadd_tree(mats)
    total = sum(a.cap for a in mats)
    rest = [with_capacity(a, max(b.cap for b in mats[1:])) for a in mats[1:]]
    keys = jnp.stack([a.keys for a in rest])
    vals = jnp.stack([a.vals for a in rest])
    shape = mats[0].shape

    def add(i, acc):
        out = _alg.two_way_add(
            PaddedCOO(*acc, shape), PaddedCOO(keys[i], vals[i], 0, shape))
        return out.keys[:total], out.vals[:total], out.nnz

    first = with_capacity(mats[0], total)
    out = jax.lax.fori_loop(0, len(rest), add,
                            (first.keys, first.vals, first.nnz))
    return PaddedCOO(*out, shape)


def _run_sorted(mats: Sequence[PaddedCOO],
                cost_model: Optional[Dict[str, float]] = None) -> PaddedCOO:
    """k-way merge regime (``spkadd.spkadd_sorted``, staged): the plan's one
    stable sort and first-occurrence flags, the segment sum of each key's
    run, the canonical values. The one regime that adds wide keys: the
    plan sorts both words in one multi-key sort."""
    with obs.stage("spkadd.plan"):
        cat = concat(mats)
        plan = compress_plan(cat.keys, cat.shape, cat.vals)
    with obs.stage("spkadd.accumulate"):
        sums = jax.ops.segment_sum(plan.sorted_vals, plan.gid,
                                   num_segments=cat.cap)
    with obs.stage("spkadd.output"):
        vals = jnp.where(jnp.arange(cat.cap) < plan.nnz, sums, 0.0)
    return PaddedCOO(keys=plan.out_keys, vals=vals, nnz=plan.nnz,
                     shape=cat.shape)


def _unphased(run):
    """A regime with no separate plan and output phases (``tree``): its
    whole call is named ``spkadd.accumulate``."""
    def staged(mats, cost_model=None):
        with obs.stage("spkadd.accumulate"):
            return run(mats, cost_model=cost_model)
    return staged


#: Engine-canonical paths: every entry returns the same PaddedCOO bitwise
#: (the per-key value folds all happen in input-stream order). Entries share
#: the signature ``(mats, cost_model=None)`` — the cost model carries
#: regime-internal knobs (today: the vec one-hot boundary), so per-call
#: overrides reach every regime uniformly.
_CANONICAL = {
    "tree": _unphased(_run_tree),
    "sorted": _run_sorted,
    "spa": _run_spa,
    "vec": _run_vec,
    "blocked_spa": _run_blocked_spa,
    "hash": _run_hash,
}


def spkadd_auto(mats: Sequence[PaddedCOO], *,
                cost_model: Optional[Dict[str, float]] = None,
                signals: Optional[RegimeSignals] = None) -> PaddedCOO:
    """``B = sum_i A_i`` with the regime's winning algorithm.

    Dispatch is static (capacity-based signals), so this function jits and
    vmaps. Pass ``signals=regime_signals(mats, exact=True)`` outside jit to
    dispatch on exact nnz/compression instead of the capacity bounds, or
    ``cost_model=`` a calibrated table (see :func:`load_cost_model`).
    """
    sig = signals if signals is not None else regime_signals(mats)
    selected = select_algorithm(sig, cost_model)
    obs.counter(f"engine.dispatch.{selected}").inc()
    with obs.span("engine.spkadd_auto", selected=selected, k=sig.k,
                  density=sig.density, compression=sig.compression,
                  accum_elems=sig.accum_elems,
                  wide_keys=is_wide(mats[0].shape)):
        return _CANONICAL[selected](mats, cost_model=cost_model)


def explain_dispatch(mats: Sequence[PaddedCOO], *,
                     cost_model: Optional[Dict[str, float]] = None,
                     exact: bool = False) -> Tuple[RegimeSignals, str]:
    """(signals, selected algorithm) — observability for callers/tests."""
    sig = regime_signals(mats, exact=exact)
    return sig, select_algorithm(sig, cost_model)


def spkadd_run(mats: Sequence[PaddedCOO], algorithm: str = "auto",
               **kw) -> PaddedCOO:
    """Single entry point for every SpKAdd consumer.

    ``algorithm="auto"`` goes through the regime dispatcher (canonical
    output); any explicit algorithm name runs the corresponding member of
    the family from :mod:`repro.core.spkadd` unchanged.
    """
    if algorithm == "auto":
        return spkadd_auto(mats, **kw)
    return _alg.spkadd(mats, algorithm=algorithm, **kw)


# ---------------------------------------------------------------------------
# batched execution
# ---------------------------------------------------------------------------

def stack_collections(collections: Sequence[Sequence[PaddedCOO]]
                      ) -> List[PaddedCOO]:
    """Stack B same-shaped collections of k matrices into one *batched*
    collection: k PaddedCOOs whose leaves carry a leading batch dim
    (keys ``(B, cap)``, vals ``(B, cap)``, nnz ``(B,)``)."""
    k = len(collections[0])
    shape = collections[0][0].shape
    for coll in collections:
        if len(coll) != k:
            raise ValueError("all collections must have the same k")
        for a in coll:
            if a.shape != shape:
                raise ValueError("stacked collections must share a shape")
    return [
        PaddedCOO(
            keys=jax.tree.map(lambda *ks: jnp.stack(ks),
                              *[coll[i].keys for coll in collections]),
            vals=jnp.stack([coll[i].vals for coll in collections]),
            nnz=jnp.stack([jnp.asarray(coll[i].nnz, jnp.int32)
                           for coll in collections]),
            shape=shape,
        )
        for i in range(k)
    ]


def unstack_collection(batched: Sequence[PaddedCOO], b: int) -> List[PaddedCOO]:
    """Slice batch element ``b`` back out of a stacked collection/result."""
    return [PaddedCOO(jax.tree.map(lambda k: k[b], a.keys), a.vals[b],
                      a.nnz[b], a.shape)
            for a in batched]


def batched_regime_signals(stacked_mats: Sequence[PaddedCOO]
                           ) -> RegimeSignals:
    """Regime signals for a stacked collection. ``regime_signals()`` can't
    be used directly: ``.cap`` on a batched leaf reads the batch dim —
    capacity is the trailing axis here."""
    m, n = stacked_mats[0].shape
    mn = m * n
    total = float(sum(a.keys.shape[-1] for a in stacked_mats))
    return RegimeSignals(k=len(stacked_mats), density=total / max(mn, 1),
                         compression=estimate_compression(total, mn),
                         accum_elems=mn)


def explain_batched_dispatch(stacked_mats: Sequence[PaddedCOO], *,
                             algorithm: str = "auto",
                             cost_model: Optional[Dict[str, float]] = None
                             ) -> Tuple[RegimeSignals, str, str]:
    """(signals, requested, effective) for a batched run — the observable
    twin of :func:`explain_dispatch`.

    ``effective`` is the algorithm :func:`spkadd_batched` actually executes.
    Since the batched partitioned launch, every canonical regime — including
    ``vec``/``blocked_spa`` — runs natively, so requested == effective; the
    field exists so any future downgrade is *reported*, never silent: the
    decision is recorded as an ``engine.batched_dispatch`` trace span, and
    an effective ≠ requested divergence additionally logs a one-line
    warning and bumps ``engine.batched.downgrades``.
    """
    sig = batched_regime_signals(stacked_mats)
    requested = (select_algorithm(sig, cost_model) if algorithm == "auto"
                 else algorithm)
    effective = requested
    with obs.span("engine.batched_dispatch", requested=requested,
                  effective=effective, k=sig.k, density=sig.density,
                  compression=sig.compression, accum_elems=sig.accum_elems,
                  batch=int(stacked_mats[0].keys.shape[0])):
        pass
    if effective != requested:
        obs.counter("engine.batched.downgrades").inc()
        _log.warning("spkadd_batched: requested algorithm %r downgraded to "
                     "%r (signals: %s)", requested, effective, sig)
    return sig, requested, effective


def _run_partitioned_batched(stacked_mats: Sequence[PaddedCOO], regime: str,
                             vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
                             cost_model: Optional[Dict[str, float]] = None
                             ) -> PaddedCOO:
    """Batched one-pass partitioned launch: B sorted streams, per-batch step
    tables, ONE Pallas program with a leading batch grid dimension — the
    shared :func:`_partitioned_core` pipeline at B > 1. The single stable
    sort per call is preserved (one vmapped key-value sort)."""
    keys, vals = _batched_streams(stacked_mats)
    return _partitioned_core(keys, vals, stacked_mats[0].shape, regime,
                             vmem_budget_bytes, cost_model)


def _batched_streams(stacked_mats: Sequence[PaddedCOO]
                     ) -> Tuple[jax.Array, jax.Array]:
    """The ``(B, cap)`` concatenated key and value streams of a stacked
    collection."""
    with obs.stage("spkadd.plan"):
        return (jnp.concatenate([a.keys for a in stacked_mats], axis=-1),
                jnp.concatenate([a.vals for a in stacked_mats], axis=-1))


def spkadd_batched(stacked_mats: Sequence[PaddedCOO], *,
                   algorithm: str = "auto",
                   cost_model: Optional[Dict[str, float]] = None) -> PaddedCOO:
    """Add B independent collections in one XLA program.

    ``stacked_mats`` is a batched collection as built by
    :func:`stack_collections`. Returns a batched PaddedCOO (leading batch
    dim on every leaf). The dispatch decision is made once for the whole
    stack (all batches share shapes/capacities, hence regime signals) and
    is observable via :func:`explain_batched_dispatch`. Pure-jnp regimes
    are vmapped; a ``vec``/``blocked_spa`` selection runs the batched
    partitioned Pallas launch (leading batch grid dimension) — no silent
    ``spa`` downgrade, and the result is bit-identical to the
    per-collection canonical output.
    """
    _, _, effective = explain_batched_dispatch(
        stacked_mats, algorithm=algorithm, cost_model=cost_model)
    if effective in ("blocked_spa", "vec"):
        return _run_partitioned_batched(stacked_mats, effective,
                                        cost_model=cost_model)
    if effective == "hash":
        # native batched sliding-hash launch (leading batch grid dimension);
        # vmapping the B = 1 path would re-trace the Pallas call per batch
        keys, vals = _batched_streams(stacked_mats)
        return _hash_core(keys, vals, stacked_mats[0].shape,
                          VMEM_BUDGET_BYTES, cost_model)

    def one(mats):
        return _CANONICAL[effective](mats, cost_model=cost_model) \
            if effective in _CANONICAL \
            else _alg.spkadd(mats, algorithm=effective)

    return jax.vmap(one)(list(stacked_mats))


# ---------------------------------------------------------------------------
# ragged batched execution (capacity bucketing)
# ---------------------------------------------------------------------------

def bucket_collections(collections: Sequence[Sequence[PaddedCOO]]):
    """Group collections by (shape, k, pow2-rounded per-matrix capacities).

    Returns ``{bucket_key: [(orig_index, padded_collection), ...]}`` where
    every matrix in a padded collection has its capacity rounded up to the
    next power of two — the rounding is what folds near-miss capacities
    into a shared bucket so one vmapped program covers them.
    """
    buckets: Dict[tuple, List[tuple]] = {}
    for i, coll in enumerate(collections):
        caps = tuple(next_pow2(a.cap) for a in coll)
        padded = [with_capacity(a, c) for a, c in zip(coll, caps)]
        key = (coll[0].shape, caps)
        buckets.setdefault(key, []).append((i, padded))
    return buckets


def spkadd_batched_ragged(collections: Sequence[Sequence[PaddedCOO]], *,
                          algorithm: str = "auto",
                          cost_model: Optional[Dict[str, float]] = None
                          ) -> List[PaddedCOO]:
    """:func:`spkadd_batched` for *ragged* stacks: per-collection capacities
    (and k) no longer have to match. Collections are bucketed by
    (shape, k, pow2-rounded capacities) — padding a capacity to the next
    power of two is free under the PaddedCOO sentinel invariant and folds
    the long tail of near-miss capacities into a handful of buckets — and
    each bucket runs as one vmapped engine program. Results come back in
    input order; a result's capacity is its bucket's rounded total (a
    superset layout of the unrounded canonical output: same leading
    distinct keys, extra sentinel slots).
    """
    results: List[Optional[PaddedCOO]] = [None] * len(collections)
    with obs.stage("spkadd.plan"):
        buckets = bucket_collections(collections)
    obs.counter("engine.ragged.calls").inc()
    with obs.span("engine.spkadd_batched_ragged", algorithm=algorithm,
                  collections=len(collections), buckets=len(buckets)):
        for _, members in buckets.items():
            obs.histogram("engine.ragged.bucket_occupancy").observe(
                len(members))
            idxs = [i for i, _ in members]
            with obs.stage("spkadd.plan"):
                stacked = stack_collections([padded for _, padded in members])
            out = spkadd_batched(stacked, algorithm=algorithm,
                                 cost_model=cost_model)
            with obs.stage("spkadd.output"):
                for b, i in enumerate(idxs):
                    results[i] = unstack_collection([out], b)[0]
    return results
