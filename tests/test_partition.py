"""One-pass stream-partitioned sliding accumulation (kernels/partition.py).

Three contracts under test:

1. **Bit-identity.** The partitioned launch (every fold) must match the
   dense oracle and the canonical engine contract bitwise — including
   part-boundary-spanning keys, empty parts, the single-part degenerate,
   duplicate-heavy streams, and ragged batches.
2. **Single-sort discipline.** The `vec`/`blocked_spa` regimes issue
   exactly one stable key sort per engine call (the canonical plan's,
   shared with the stream partition) — counted via ``sparse.sort_calls``.
3. **I/O optimality.** The modeled input-chunk loads equal the lower bound
   (each non-empty chunk once), not the legacy ``parts × num_chunks``.

Shapes are tiny on purpose: interpret-mode Pallas dominates tier-1 runtime.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import engine as E
from repro.core import sparse as S
from repro.core.spkadd import spkadd
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.partition import modeled_chunk_loads

FOLDS = ["serial", "onehot"]

#: cost-model override forcing the vec regime regardless of shape.
FORCE_VEC = {"tree_max_k": 0, "spa_max_accum_elems": 1.0,
             "vec_min_density": 0.0, "vec_max_accum_elems": float(1 << 40)}
FORCE_BLOCKED = {"tree_max_k": 0, "spa_max_accum_elems": 1.0,
                 "vec_max_accum_elems": 1.0, "blocked_spa_min_density": 0.0,
                 "blocked_spa_max_accum_elems": float(1 << 40)}


def random_collection(seed, k, m, n, nnz):
    rng = np.random.default_rng(seed)
    mats = []
    for _ in range(k):
        d = np.zeros((m, n), np.float32)
        take = min(nnz, m * n)
        idx = rng.choice(m * n, take, replace=False)
        d.flat[idx] = rng.standard_normal(take)
        mats.append(S.from_dense(jnp.asarray(d), cap=nnz))
    return mats


def assert_bit_identical(a: S.PaddedCOO, b: S.PaddedCOO, msg=""):
    assert a.shape == b.shape and a.cap == b.cap, msg
    assert int(a.nnz) == int(b.nnz), msg
    np.testing.assert_array_equal(np.asarray(a.keys), np.asarray(b.keys),
                                  err_msg=msg)
    np.testing.assert_array_equal(np.asarray(a.vals), np.asarray(b.vals),
                                  err_msg=msg)


def run_partitioned(keys, vals, *, m, n, part_elems, chunk, fold):
    """plan_and_partition + the raw wrapper, as the engine wires them."""
    geom = kops.partitioned_launch_geometry(len(keys), m=m, n=n,
                                            part_elems=part_elems,
                                            chunk=chunk)
    plan, keys_p, steps = S.plan_and_partition(
        keys, (m, n), part_elems=geom.part_elems, chunk=geom.chunk, vals=vals)
    vals_p = jnp.zeros(keys_p.shape, jnp.float32).at[:len(keys)].set(
        plan.sorted_vals.astype(jnp.float32))
    acc = kops.partitioned_accumulate(
        keys_p, vals_p, steps.chunk_id, steps.part_id, m=m, n=n,
        part_elems=geom.part_elems, parts=geom.parts, chunk=geom.chunk,
        fold=fold)
    return np.asarray(acc).reshape(-1)[:m * n]


def flat_ref(keys, vals, *, m, n):
    return np.asarray(ref.spa_accumulate_ref(keys, vals,
                                             m=m, n=n)).T.reshape(-1)


# ---------------------------------------------------------------------------
# kernel bit-exactness across partition geometries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fold", FOLDS)
@pytest.mark.parametrize("m,n,nnz,part_elems,chunk", [
    (16, 6, 40, 32, 8),     # 3 parts, boundary chunks span parts
    (32, 8, 100, 256, 16),  # single-part degenerate
    (16, 4, 50, 8, 8),      # tiny parts: many empty + multi-part chunks
    (24, 4, 30, 128, 32),   # chunk > nnz: sentinel-tail padding
    (8, 16, 200, 16, 8),    # more inputs than slots: runs cross chunks
])
def test_partitioned_bitwise_vs_oracle(fold, m, n, nnz, part_elems, chunk):
    rng = np.random.default_rng(hash((m, n, nnz)) % 2**31)
    keys = jnp.asarray(rng.integers(0, m * n, nnz).astype(np.int32))
    vals = jnp.asarray(rng.standard_normal(nnz).astype(np.float32))
    got = run_partitioned(keys, vals, m=m, n=n, part_elems=part_elems,
                          chunk=chunk, fold=fold)
    np.testing.assert_array_equal(np.asarray(got), flat_ref(keys, vals, m=m, n=n),
                                  err_msg=f"{fold}")


@pytest.mark.parametrize("fold", FOLDS)
def test_partitioned_boundary_spanning_key_runs(fold):
    """A duplicate run sitting exactly at a part boundary key and spilling
    across chunk boundaries must keep the left-fold chain: duplicates of
    one key always belong to ONE part, so the fold continues across that
    part's consecutive steps."""
    m, n, E_ = 8, 8, 16  # parts of 16 keys; key 16 is a boundary key
    rng = np.random.default_rng(3)
    keys = np.concatenate([np.full(20, 15), np.full(20, 16), np.full(3, 63)])
    vals = rng.standard_normal(len(keys)).astype(np.float32)
    kj, vj = jnp.asarray(keys.astype(np.int32)), jnp.asarray(vals)
    got = run_partitioned(kj, vj, m=m, n=n, part_elems=E_, chunk=8, fold=fold)
    np.testing.assert_array_equal(np.asarray(got), flat_ref(kj, vj, m=m, n=n))


@pytest.mark.parametrize("fold", FOLDS)
def test_partitioned_empty_parts_and_all_sentinel(fold):
    """Parts with no keys must still come back zero-initialized (their tile
    is visited once on a borrowed chunk); the all-sentinel stream is the
    every-part-empty extreme."""
    m, n, E_ = 16, 8, 16  # 8 parts
    keys = jnp.asarray(np.array([0, 1, 127, 126, 0], np.int32))  # parts 0+7
    vals = jnp.asarray(np.ones(5, np.float32))
    got = run_partitioned(keys, vals, m=m, n=n, part_elems=E_, chunk=8,
                          fold=fold)
    np.testing.assert_array_equal(np.asarray(got), flat_ref(keys, vals, m=m, n=n))

    sent = jnp.full((12,), m * n, jnp.int32)
    zero = jnp.zeros((12,), jnp.float32)
    got = run_partitioned(sent, zero, m=m, n=n, part_elems=E_, chunk=8,
                          fold=fold)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(m * n, np.float32))


@pytest.mark.parametrize("fold", FOLDS)
def test_partitioned_duplicate_heavy(fold):
    """90% duplicates: long runs spanning many chunks of one part."""
    rng = np.random.default_rng(7)
    uniq = rng.choice(128, 12, replace=False)
    keys = np.concatenate([uniq, rng.choice(uniq, 108)]).astype(np.int32)
    rng.shuffle(keys)
    vals = rng.standard_normal(len(keys)).astype(np.float32)
    kj, vj = jnp.asarray(keys), jnp.asarray(vals)
    got = run_partitioned(kj, vj, m=16, n=8, part_elems=32, chunk=16,
                          fold=fold)
    np.testing.assert_array_equal(np.asarray(got), flat_ref(kj, vj, m=16, n=8))


# ---------------------------------------------------------------------------
# engine integration: canonical contract through the partitioned path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("force", [FORCE_VEC, FORCE_BLOCKED],
                         ids=["vec", "blocked_spa"])
def test_engine_partitioned_bit_identical(force):
    mats = random_collection(11, 8, 48, 8, 36)
    ref_out = spkadd(mats, algorithm="sorted")
    out = E.spkadd_auto(mats, cost_model=force)
    assert_bit_identical(ref_out, out)


def test_engine_partitioned_multi_part_geometry():
    """Force parts > 1 through the engine by shrinking part_elems via a
    small VMEM budget in the kernel wrapper's geometry helper."""
    mats = random_collection(12, 6, 64, 8, 40)
    geom = kops.partitioned_launch_geometry(
        sum(a.cap for a in mats), m=64, n=8, vmem_budget_bytes=512)
    assert geom.parts > 1
    cat = S.concat(mats)
    plan, keys_p, steps = S.plan_and_partition(
        cat.keys, cat.shape, part_elems=geom.part_elems, chunk=geom.chunk,
        vals=cat.vals)
    vals_p = jnp.zeros(keys_p.shape, jnp.float32).at[:cat.cap].set(
        plan.sorted_vals)
    acc = kops.partitioned_accumulate(
        keys_p, vals_p, steps.chunk_id, steps.part_id, m=64, n=8,
        part_elems=geom.part_elems, parts=geom.parts, chunk=geom.chunk,
        fold="serial")
    np.testing.assert_array_equal(
        np.asarray(acc).reshape(-1)[:64 * 8],
        flat_ref(cat.keys, cat.vals, m=64, n=8))


def test_engine_single_stable_sort_per_call():
    """The acceptance contract: one stable sort per spkadd_auto call in the
    partitioned regimes (the plan's sort, shared with the partition) —
    the old vec path paid two (plan + in-wrapper pre-sort)."""
    mats = random_collection(13, 8, 48, 8, 36)
    for force in (FORCE_VEC, FORCE_BLOCKED):
        before = S.sort_calls()
        E.spkadd_auto(mats, cost_model=force)
        assert S.sort_calls() - before == 1, force


def test_engine_batched_single_stable_sort():
    colls = [random_collection(20 + b, 4, 32, 8, 24) for b in range(3)]
    stacked = E.stack_collections(colls)
    before = S.sort_calls()
    E.spkadd_batched(stacked, cost_model=FORCE_VEC)
    assert S.sort_calls() - before == 1


def test_lowered_hlo_contains_single_sort():
    """Defense in depth for the sort counter: the jitted vec-regime program
    lowers to exactly one sort op."""
    mats = random_collection(14, 8, 48, 8, 36)
    lowered = jax.jit(
        lambda ms: E.spkadd_auto(ms, cost_model=FORCE_VEC)).lower(mats)
    text = lowered.as_text()
    # the StableHLO sort op, not substrings like `call @argsort(`
    n_sorts = text.count('"stablehlo.sort"') + text.count("stablehlo.sort(")
    assert n_sorts == 1, f"expected exactly 1 sort op in HLO, found {n_sorts}"


# ---------------------------------------------------------------------------
# batched partitioned launch (no downgrade) + ragged batches
# ---------------------------------------------------------------------------

def test_batched_vec_stays_vec_and_matches_per_collection():
    """The satellite contract: a vec selection on a batched stack runs the
    partitioned Pallas launch (reported effective == vec, no spa fallback)
    and is bit-identical to the per-collection canonical result."""
    colls = [random_collection(300 + b, 8, 32, 8, 30) for b in range(3)]
    stacked = E.stack_collections(colls)
    _, requested, effective = E.explain_batched_dispatch(
        stacked, cost_model=FORCE_VEC)
    assert requested == "vec" and effective == "vec"
    out = E.spkadd_batched(stacked, cost_model=FORCE_VEC)
    for b, coll in enumerate(colls):
        want = spkadd(coll, algorithm="sorted")
        assert_bit_identical(want, E.unstack_collection([out], b)[0],
                             msg=f"batch {b}")


@pytest.mark.parametrize("algorithm", ["vec", "blocked_spa"])
def test_batched_explicit_partitioned_regimes(algorithm):
    colls = [random_collection(400 + b, 8, 32, 8, 30) for b in range(2)]
    stacked = E.stack_collections(colls)
    _, requested, effective = E.explain_batched_dispatch(
        stacked, algorithm=algorithm)
    assert requested == algorithm and effective == algorithm
    out = E.spkadd_batched(stacked, algorithm=algorithm)
    for b, coll in enumerate(colls):
        assert_bit_identical(spkadd(coll, algorithm="sorted"),
                             E.unstack_collection([out], b)[0])


def test_batched_ragged_partitioned_matches_engine():
    """Ragged stacks (different caps and k) through the vec regime: each
    bucket runs the batched partitioned launch; results match the
    per-collection engine in input order."""
    colls = [random_collection(30, 4, 32, 8, 24),
             random_collection(31, 4, 32, 8, 17),   # same bucket as [0]
             random_collection(32, 3, 32, 8, 24),   # different k
             random_collection(33, 4, 32, 8, 65)]   # different bucket
    outs = E.spkadd_batched_ragged(colls, algorithm="vec")
    for coll, out in zip(colls, outs):
        want = E._CANONICAL["vec"](coll)
        assert int(out.nnz) == int(want.nnz)
        np.testing.assert_array_equal(np.asarray(out.to_dense()),
                                      np.asarray(want.to_dense()))


def test_batched_under_jit():
    colls = [random_collection(500 + b, 8, 32, 8, 20) for b in range(2)]
    stacked = E.stack_collections(colls)
    out = jax.jit(lambda s: E.spkadd_batched(s, cost_model=FORCE_VEC))(stacked)
    eager = E.spkadd_batched(stacked, cost_model=FORCE_VEC)
    np.testing.assert_array_equal(np.asarray(out.keys), np.asarray(eager.keys))
    np.testing.assert_array_equal(np.asarray(out.vals), np.asarray(eager.vals))


# ---------------------------------------------------------------------------
# I/O accounting (the tentpole's perf claim, measurable without a TPU)
# ---------------------------------------------------------------------------

def test_modeled_loads_one_pass_vs_all_pairs():
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 512, 300).astype(np.int32)
    r = modeled_chunk_loads(keys, mn=512, part_elems=64, parts=8, chunk=32)
    assert r["legacy_all_pairs"] == r["parts"] * r["num_chunks"]
    assert r["onepass"] == r["lower_bound"]
    assert r["onepass"] < r["legacy_all_pairs"]


def test_modeled_loads_skip_sentinel_tail():
    """Chunks holding only sentinel padding are never scheduled."""
    keys = np.concatenate([np.arange(10), np.full(54, 512)]).astype(np.int32)
    r = modeled_chunk_loads(keys, mn=512, part_elems=256, parts=2, chunk=16)
    assert r["onepass"] == 1  # ten keys -> one non-empty chunk
    assert r["num_chunks"] == 4


def test_modeled_loads_empty_parts_add_no_loads():
    """Empty parts borrow the previous step's resident chunk."""
    keys = np.array([0, 1, 2, 3, 500, 501], np.int32)  # parts 0 and 7 only
    r = modeled_chunk_loads(keys, mn=512, part_elems=64, parts=8, chunk=8)
    assert r["onepass"] == r["lower_bound"] == 1
    assert r["steps"] >= r["parts"]  # every part still visited


def test_step_tables_monotone_and_bounded():
    """part_id/chunk_id non-decreasing (the consecutive-revisit invariant
    the Pallas accumulation pattern needs) and within the static bound."""
    rng = np.random.default_rng(9)
    keys = jnp.asarray(np.sort(rng.integers(0, 128, 96)).astype(np.int32))
    steps = S.partition_steps(keys, mn=128, part_elems=16, parts=8, chunk=16)
    p, c = np.asarray(steps.part_id), np.asarray(steps.chunk_id)
    assert (np.diff(p) >= 0).all() and (np.diff(c) >= 0).all()
    assert len(p) == S.partition_max_steps(96 // 16, 8)
    assert c.max() < 96 // 16 and p.max() <= 8


# ---------------------------------------------------------------------------
# choose_block_rows regression (satellite: round DOWN to the lane multiple)
# ---------------------------------------------------------------------------

def test_choose_block_rows_never_exceeds_budget():
    """The chosen tile must fit vmem_budget_bytes exactly (no round-up past
    the budget); the floor at 8 sublanes is the only sanctioned excess."""
    for n in (1, 8, 32, 64, 100):
        for budget in (4096, 9 * n * 4, 16 * 1024, 1 << 20):
            br = kops.choose_block_rows(1 << 16, n, budget)
            assert br % 8 == 0
            if budget >= 8 * n * 4:  # budget can hold the minimum tile
                assert br * n * 4 <= budget, (n, budget, br)
            else:
                assert br == 8  # documented floor


def test_partitioned_geometry_budget_discipline():
    """part_elems rounds DOWN to the lane multiple under the budget NET of
    the double-buffered input chunk blocks — the whole launch footprint
    (both pipeline buffers of the tile + 2×(keys, vals) chunks) fits VMEM
    whenever the budget can hold the floor tile at all (floor: one lane
    multiple)."""
    for budget in (512, 700, 4096, 1 << 20):
        geom = kops.partitioned_launch_geometry(1024, m=512, n=64,
                                                vmem_budget_bytes=budget)
        footprint = 2 * geom.part_elems * 4 + 2 * geom.chunk * 8
        if budget >= 2 * 128 * 4 + 2 * geom.chunk * 8:
            assert footprint <= budget, (budget, footprint)
        else:
            assert geom.part_elems == 128  # documented floor
        assert geom.part_elems % 128 == 0
        assert geom.parts * geom.part_elems >= 512 * 64
