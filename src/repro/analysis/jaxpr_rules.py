"""Layer-1 spkaddlint rules: jaxpr checks over the public engine surface.

Every public entry point is traced with abstract inputs across a geometry
matrix (shapes x k x regime x batch shape) and the *closed jaxpr* — the
program jax will actually run — is checked against the engine's contracts:

- SPKJ201 one-sort: count ``sort`` primitives recursively (through pjit /
  scan / cond / vmap sub-jaxprs) and compare to the regime's expected
  count. This generalizes the single-HLO-sort pin in
  ``tests/test_partition.py`` from one regime to the whole entry-point
  surface.
- SPKJ202 index-dtype: no int64/uint64 operand may reach a ``pallas_call``
  eqn — index arithmetic is int32 end to end.
- SPKJ203 step-table: re-derive the partition schedule on concrete
  geometry and prove every payload (chunk, part) pair is scheduled exactly
  once with non-decreasing tables (consecutive output-tile revisits).
- SPKJ204 vmem-budget: see :mod:`repro.analysis.vmem`.

Tracing is staging only — no kernel executes; the matrix keeps shapes tiny
so a full run stays in single-digit seconds.
"""
from __future__ import annotations

from typing import Callable, Iterable, List, Tuple

import numpy as np

from repro.analysis.findings import Finding

# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _subjaxprs(params: dict):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for item in vs:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def iter_eqns(jaxpr):
    """Every eqn in a (Closed)Jaxpr, recursing into sub-jaxpr params
    (pjit, scan, while, cond branches, custom_* call jaxprs, ...)."""
    from jax.extend.core import ClosedJaxpr
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub)


def count_sorts(closed) -> int:
    """Number of ``sort`` primitives in the whole program."""
    return sum(1 for e in iter_eqns(closed) if e.primitive.name == "sort")


BAD_INDEX_DTYPES = ("int64", "uint64")


def index_dtype_findings(closed, label: str) -> List[Finding]:
    """SPKJ202 over one traced program: every pallas_call operand aval must
    carry a 32-bit-or-narrower dtype."""
    findings: List[Finding] = []
    for eqn in iter_eqns(closed):
        if eqn.primitive.name != "pallas_call":
            continue
        for var in eqn.invars:
            aval = getattr(var, "aval", None)
            dtype = str(getattr(aval, "dtype", ""))
            if dtype in BAD_INDEX_DTYPES:
                findings.append(Finding(
                    "SPKJ202", f"<jaxpr:{label}>", 0,
                    f"{dtype} operand (shape "
                    f"{getattr(aval, 'shape', '?')}) reaches pallas_call",
                    "cast indices with .astype(jnp.int32) before the "
                    "launch wrapper; audit for implicit x64 promotion"))
    return findings


# ---------------------------------------------------------------------------
# geometry matrix: entry-point traces with expected sort counts
# ---------------------------------------------------------------------------

#: cost-model overrides that force each regime regardless of signals
#: (the canonical copies — tests/test_partition.py mirrors VEC/BLOCKED).
REGIME_FORCES = {
    "tree": {"tree_max_k": 1e9},
    "sorted": {"tree_max_k": 0, "spa_max_accum_elems": 0.0,
               "hash_min_total_nnz": 1e18,
               "vec_max_accum_elems": 0.0,
               "blocked_spa_max_accum_elems": 0.0},
    "spa": {"tree_max_k": 0, "spa_max_accum_elems": float(1 << 40),
            "spa_min_density": 0.0, "spa_min_compression": 0.0},
    "hash": {"tree_max_k": 0, "spa_max_accum_elems": 0.0,
             "hash_min_total_nnz": 0.0, "hash_max_compression": 1e9,
             "hash_max_table_elems": float(1 << 40)},
    "vec": {"tree_max_k": 0, "spa_max_accum_elems": 1.0,
            "hash_min_total_nnz": 1e18,
            "vec_min_density": 0.0, "vec_max_accum_elems": float(1 << 40)},
    "blocked_spa": {"tree_max_k": 0, "spa_max_accum_elems": 1.0,
                    "hash_min_total_nnz": 1e18,
                    "vec_max_accum_elems": 1.0,
                    "blocked_spa_min_density": 0.0,
                    "blocked_spa_max_accum_elems": float(1 << 40)},
}


def expected_sorts(regime: str, k: int) -> int:
    """The one-sort invariant, per regime: the partitioned/sorted/spa
    regimes share the single canonical-plan sort; the sort-free ``hash``
    regime pays zero sorts before accumulation and exactly one at
    compaction (so one total); the tree regime pays one compress per
    2-way add (k-1 of them, floored at the k=1 compress)."""
    if regime == "tree":
        return max(1, k - 1)
    return 1


def _collection(seed: int, k: int, m: int, n: int, nnz: int):
    """Deterministic tiny collection (host-side build; sorts here do not
    appear in the traced programs below, which close over the arrays)."""
    import jax.numpy as jnp
    from repro.core import sparse as S

    rng = np.random.default_rng(seed)
    mats = []
    for _ in range(k):
        d = np.zeros((m, n), np.float32)
        take = min(nnz, m * n)
        idx = rng.choice(m * n, take, replace=False)
        d.flat[idx] = rng.standard_normal(take)
        mats.append(S.from_dense(jnp.asarray(d), cap=nnz))
    return mats


def geometry_matrix() -> Iterable[Tuple[str, Callable[[], object], int]]:
    """Yield (label, zero-arg traceable thunk, expected sort count) for
    every public entry point x geometry cell."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine as E
    from repro.core import streaming as STR
    from repro.core import allreduce as AR
    from repro.core.topk import SparseUpdate
    from repro import compat
    from jax.sharding import PartitionSpec as P

    shapes = [(16, 4), (64, 8)]
    ks = [1, 3, 5]
    for (m, n) in shapes:
        for k in ks:
            mats = _collection(7 * m + k, k, m, n, max(4, m * n // 8))
            for regime, force in REGIME_FORCES.items():
                if regime == "tree" and k > 3:
                    continue  # forced-tree beyond the canonical band is a
                    # left fold; covered at k<=3
                yield (f"spkadd_auto[{regime},k={k},{m}x{n}]",
                       lambda mats=mats, force=force:
                       E.spkadd_auto(mats, cost_model=dict(force)),
                       expected_sorts(regime, k))

    # wide keys (m*n >= 2**31): one multi-key sort of both words, at any k
    from repro.core.sparse import from_coords
    wide = (1 << 16, 1 << 16)
    for k in ks:
        idx = jnp.arange(8, dtype=jnp.int32)
        mats = [from_coords(idx * 4099 % wide[0], (idx + i) % 3 * 21845,
                            jnp.ones((8,), jnp.float32), wide, nnz=6)
                for i in range(k)]
        yield (f"spkadd_auto[wide,k={k}]",
               lambda mats=mats: E.spkadd_auto(mats), 1)

    # batched: one vmapped sort for the whole stack (hash: the single
    # batched compaction sort — still one)
    colls = [_collection(100 + b, 4, 32, 8, 24) for b in range(3)]
    stacked = E.stack_collections(colls)
    for regime in ("vec", "blocked_spa", "hash"):
        force = REGIME_FORCES[regime]
        yield (f"spkadd_batched[{regime},B=3]",
               lambda stacked=stacked, force=force:
               E.spkadd_batched(stacked, cost_model=dict(force)),
               1)

    # ragged: one sort per capacity bucket
    ragged = [_collection(200, 3, 16, 4, 8), _collection(201, 3, 16, 4, 8),
              _collection(202, 3, 16, 4, 30)]  # 2 buckets (8->8, 30->32)
    force = REGIME_FORCES["vec"]
    yield ("spkadd_batched_ragged[vec,buckets=2]",
           lambda ragged=ragged, force=force:
           E.spkadd_batched_ragged(ragged, cost_model=dict(force)),
           2)

    # streaming flush (functional core): one engine sort + the
    # truncate-by-magnitude re-sort of the budgeted running state
    fmats = _collection(300, 4, 16, 4, 12)
    from repro.core.sparse import make_empty
    running = make_empty((16, 4), cap=8)
    yield ("streaming.flush[vec,k=4]",
           lambda fmats=fmats, running=running, force=force:
           STR._truncate_by_magnitude(
               E.spkadd_run([running] + fmats, cost_model=dict(force)),
               running.cap),
           2)

    # sparse allreduce, gather_kway with the vec accumulator: the local
    # k-way fold's single pre-sort
    if jax.device_count() >= 1:
        mesh = compat.make_mesh((1,), ("dp",), devices=jax.devices()[:1])
        u = SparseUpdate(idx=jnp.arange(8, dtype=jnp.int32),
                         val=jnp.ones((8,), jnp.float32), size=64)

        def _allreduce(u=u, mesh=mesh):
            f = compat.shard_map(
                lambda uu: AR.sparse_allreduce(uu, "dp", "gather_kway",
                                               accumulator="vec"),
                mesh=mesh, in_specs=(P("dp"),), out_specs=P(None),
                check_vma=False)
            return f(SparseUpdate(u.idx[None], u.val[None], u.size))

        yield ("sparse_allreduce[gather_kway,vec]", _allreduce, 1)


def check_entry_points() -> List[Finding]:
    """SPKJ201 + SPKJ202 over the whole geometry matrix."""
    import jax

    findings: List[Finding] = []
    for label, thunk, expected in geometry_matrix():
        try:
            closed = jax.make_jaxpr(thunk)()
        except Exception as e:  # an untraceable entry point is a finding
            findings.append(Finding(
                "SPKJ201", f"<jaxpr:{label}>", 0,
                f"entry point failed to trace: {type(e).__name__}: {e}",
                "keep every public engine entry point traceable with "
                "abstract inputs"))
            continue
        n = count_sorts(closed)
        if n != expected:
            findings.append(Finding(
                "SPKJ201", f"<jaxpr:{label}>", 0,
                f"{n} sort primitive(s) in the closed jaxpr, expected "
                f"{expected}",
                "route every key sort through sparse.stable_sort_pairs and "
                "share the canonical plan's sort (plan_and_partition) "
                "instead of re-sorting"))
        findings.extend(index_dtype_findings(closed, label))
    return findings


# ---------------------------------------------------------------------------
# SPKJ203: step-table legality
# ---------------------------------------------------------------------------


def validate_step_tables(chunk_id: np.ndarray, part_id: np.ndarray, *,
                         keys_sorted: np.ndarray, mn: int, part_elems: int,
                         parts: int, chunk: int,
                         label: str = "") -> List[Finding]:
    """Prove one (chunk_id, part_id) schedule legal for a sorted stream.

    Legality = (a) both tables non-decreasing (consecutive output-tile
    revisits — the Pallas accumulation pattern), (b) every payload
    (chunk, part) pair scheduled exactly once (no double accumulation, no
    dropped payload), (c) no real pair scheduled twice.
    """
    where = f"<steps:{label or f'mn={mn},parts={parts},chunk={chunk}'}>"
    findings: List[Finding] = []

    def emit(msg: str, fixit: str) -> None:
        findings.append(Finding("SPKJ203", where, 0, msg, fixit))

    chunk_id = np.asarray(chunk_id)
    part_id = np.asarray(part_id)
    if np.any(np.diff(part_id) < 0):
        emit("part_id table is not non-decreasing — output-tile revisits "
             "would be non-consecutive (illegal Pallas accumulation)",
             "partition_steps must emit parts in ascending key order")
    if np.any(np.diff(chunk_id) < 0):
        emit("chunk_id table is not non-decreasing — chunks would be "
             "re-fetched after eviction (breaks the I/O bound)",
             "partition_steps must sweep chunks forward only")

    # payload pairs the schedule must cover exactly once
    keys = np.asarray(keys_sorted)
    valid = keys < mn
    pos = np.nonzero(valid)[0]
    required = {(int(p // chunk), int(k // part_elems))
                for p, k in zip(pos, keys[valid])}
    real = [(int(c), int(p)) for c, p in zip(chunk_id, part_id) if p < parts]
    seen = set()
    dup = set()
    for pair in real:
        (dup if pair in seen else seen).add(pair)
    missing = required - seen
    if dup:
        emit(f"(chunk, part) pair(s) scheduled more than once: "
             f"{sorted(dup)[:4]} — the fold would double-count them",
             "each chunk may be folded into a part at most once")
    if missing:
        emit(f"payload (chunk, part) pair(s) never scheduled: "
             f"{sorted(missing)[:4]} — their nonzeros would be dropped",
             "every chunk holding a part's keys must get a step")
    return findings


#: step-table geometry sweep: (mn, part_elems, chunk, nnz) cells covering
#: part boundaries mid-chunk, empty parts, the single-part degenerate, and
#: all-sentinel streams.
STEP_MATRIX = [
    (64 * 8, 128, 8, 100),
    (64 * 8, 128, 8, 0),
    (64 * 8, 512, 8, 40),    # single part
    (16 * 4, 128, 8, 10),    # part_elems > mn
    (1024, 128, 64, 7),      # sparse stream, most parts empty
]


def check_step_tables() -> List[Finding]:
    import jax.numpy as jnp
    from repro.core.sparse import partition_steps

    findings: List[Finding] = []
    rng = np.random.default_rng(0)
    for mn, part_elems, chunk, nnz in STEP_MATRIX:
        parts = max(1, (mn + part_elems - 1) // part_elems)
        keys = np.sort(rng.choice(mn, size=min(nnz, mn), replace=False)) \
            if nnz else np.zeros((0,), np.int64)
        cap_pad = ((max(len(keys), 1) + chunk - 1) // chunk) * chunk
        keys_p = np.full((cap_pad,), mn, np.int32)
        keys_p[:len(keys)] = keys.astype(np.int32)
        steps = partition_steps(jnp.asarray(keys_p), mn=mn,
                                part_elems=part_elems, parts=parts,
                                chunk=chunk)
        findings.extend(validate_step_tables(
            np.asarray(steps.chunk_id), np.asarray(steps.part_id),
            keys_sorted=keys_p, mn=mn, part_elems=part_elems, parts=parts,
            chunk=chunk,
            label=f"mn={mn},pe={part_elems},chunk={chunk},nnz={nnz}"))
    return findings


def run() -> List[Finding]:
    """All jaxpr-layer rules (SPKJ201-204)."""
    from repro.analysis import vmem

    findings = check_entry_points()
    findings.extend(check_step_tables())
    findings.extend(vmem.check_all())
    return findings
