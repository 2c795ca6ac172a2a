"""Key spaces at or past 2**31: two-word keys through the engine's normal
entry, against a numpy int64 / float64 reference.

A shape with ``m*n >= 2**31`` keeps its CSC keys as ``WideKeys(col, row)``,
two int32 words; ``spkadd_auto`` sends such a collection to ``sorted``,
whose plan is one multi-key sort. The canonical contract is the narrow
one: the distinct keys ascending, ``nnz`` their count, sentinel padding
``(n, 0)`` with values exactly 0, each value the float32 sum of its terms.
"""
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import jaxpr_rules as JR
from repro.core import engine as E
from repro.core import sparse as S
from repro.core.spkadd import spkadd_sorted
from repro.obs import metrics

SHAPES = [(1 << 17, 1 << 17), (100_003, 70_001)]  # m*n = 2**34, ~2**32.7
CAP = 24


def key_pool(shape, rng):
    """Distinct (col, row) pairs: every pair of edge columns and rows
    (0, n - 1, m - 1, a column whose int32 key ``col*m`` would wrap), so
    some keys differ only in the column word and some only in the row word,
    plus random pairs."""
    m, n = shape
    cols = {0, 1, (1 << 32) // m % n, n // 2, n - 1}
    rows = {0, 1, m // 3, m - 1}
    edge = {(c, r) for c in cols for r in rows}
    rand = set(zip(rng.integers(0, n, CAP).tolist(),
                   rng.integers(0, m, CAP).tolist()))
    return np.array(sorted(edge | rand), np.int64)


def wide_collection(seed, shape, k):
    """k matrices of capacity ``CAP``, a partial ``nnz`` each with padding
    after it. Each matrix draws its keys from one pool without replacement,
    and matrix 0 repeats one key, so a key has at most k + 1 terms."""
    rng = np.random.default_rng(seed)
    pool = key_pool(shape, rng)
    mats = []
    for i in range(k):
        nnz = int(rng.integers(CAP // 2, CAP + 1))
        pick = pool[rng.choice(len(pool), CAP, replace=False)]
        if i == 0:
            pick[1] = pick[0]
        vals = rng.standard_normal(CAP).astype(np.float32)
        mats.append(S.from_coords(jnp.asarray(pick[:, 1], jnp.int32),
                                  jnp.asarray(pick[:, 0], jnp.int32),
                                  jnp.asarray(vals), shape, nnz=nnz))
    return mats


def reference(mats, value_dtype=np.float64):
    """(distinct int64 keys ascending, their sums, sum of |terms|)."""
    m, _ = mats[0].shape
    keys, vals = [], []
    for a in mats:
        nnz = int(a.nnz)
        keys.append(np.asarray(a.keys.col[:nnz], np.int64) * m
                    + np.asarray(a.keys.row[:nnz]))
        vals.append(np.asarray(a.vals[:nnz]))
    uniq, inv = np.unique(np.concatenate(keys), return_inverse=True)
    v = np.concatenate(vals)
    terms = v.astype(value_dtype).astype(np.float64)
    return (uniq, np.bincount(inv, weights=terms, minlength=len(uniq)),
            np.bincount(inv, weights=np.abs(v.astype(np.float64)),
                        minlength=len(uniq)))


def val_err(got_vals, ref):
    _, sums, mags = ref
    d = len(sums)
    return float(np.max(np.abs(np.asarray(got_vals[:d], np.float64) - sums)
                        / mags))


def assert_canonical(out, ref, shape):
    """Exact keys, ``nnz`` and padding against the reference."""
    m, n = shape
    uniq = ref[0]
    d = len(uniq)
    col, row = np.asarray(out.keys.col), np.asarray(out.keys.row)
    assert col.dtype == row.dtype == np.int32
    assert int(out.nnz) == d
    np.testing.assert_array_equal(col[:d].astype(np.int64) * m + row[:d],
                                  uniq)
    assert (col[d:] == n).all() and (row[d:] == 0).all()
    assert (np.asarray(out.vals)[d:] == 0).all()


@pytest.mark.parametrize("shape", SHAPES, ids=["2^17", "100003x70001"])
@pytest.mark.parametrize("k", [2, 3, 64])
def test_wide_sum_matches_int64_reference(shape, k):
    mats = wide_collection(1000 + k, shape, k)
    assert isinstance(mats[0].keys, S.WideKeys)
    # the layout decides; no cost model moves a wide collection elsewhere
    assert E.explain_dispatch(mats)[1] == "sorted"
    assert E.explain_dispatch(
        mats, cost_model=JR.REGIME_FORCES["hash"])[1] == "sorted"

    wide = metrics.counter(S.WIDE_SORT_COUNTER_NAME)
    w0, s0 = wide.value, S.sort_calls()
    out = jax.jit(lambda ms: E.spkadd_auto(ms))(mats)
    assert (wide.value - w0, S.sort_calls() - s0) == (1, 1)

    ref = reference(mats)
    assert_canonical(out, ref, shape)
    total = sum(int(a.nnz) for a in mats)
    assert E.regime_signals(mats, exact=True).compression == \
        total / len(ref[0])
    bound = k * 2.0 ** -24  # float32 rounding of a sum of up to k + 1 terms
    assert val_err(out.vals, ref) <= bound
    # the bound is tight enough that a bfloat16 sum fails it
    assert val_err(np.asarray(ref[1], np.float32),
                   reference(mats, jnp.bfloat16)) > bound


@pytest.mark.parametrize("algorithm", ["spa", "vec", "blocked_spa", "hash"])
def test_int32_only_regimes_refuse_wide_keys(algorithm):
    mats = wide_collection(7, SHAPES[0], 4)
    with pytest.raises(ValueError, match=r"2\*\*31"):
        E.spkadd_run(mats, algorithm=algorithm)


def test_wide_program_is_int32_with_one_sort():
    mats = wide_collection(8, SHAPES[1], 5)
    closed = jax.make_jaxpr(E.spkadd_auto)(mats)
    dtypes = {str(v.aval.dtype) for e in closed.jaxpr.eqns
              for v in (*e.invars, *e.outvars) if hasattr(v, "aval")}
    assert not dtypes & {"int64", "uint64"}
    assert JR.count_sorts(closed) == 1


def test_batched_wide_collections_match_single_runs():
    colls = [wide_collection(20 + b, SHAPES[0], 4) for b in range(2)]
    out = E.spkadd_batched(E.stack_collections(colls))
    for b, coll in enumerate(colls):
        got = E.unstack_collection([out], b)[0]
        want = E.spkadd_auto(coll)
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("build", ["sentinel_key", "make_empty",
                                   "from_coords"])
def test_constructors_take_the_wide_layout(build):
    m, n = shape = SHAPES[1]
    if build == "sentinel_key":
        assert S.sentinel_key(shape) == S.WideKeys(n, 0)
        assert S.sentinel_key((46_340, 46_340)) == 46_340 ** 2  # < 2**31
        return
    if build == "make_empty":
        a = S.make_empty(shape, 5)
    else:
        rows = jnp.asarray([m - 1, 0, 3, 4, 5], jnp.int32)
        cols = jnp.asarray([n - 1, n - 1, 2, 2, 2], jnp.int32)
        a = S.from_coords(rows, cols, jnp.ones(5), shape, nnz=2)
        np.testing.assert_array_equal(a.keys.col[:2], [n - 1, n - 1])
        np.testing.assert_array_equal(a.keys.row[:2], [m - 1, 0])
        np.testing.assert_array_equal(a.rows[:2], [m - 1, 0])
    pad = slice(int(a.nnz), None)
    assert (np.asarray(a.keys.col[pad]) == n).all()
    assert (np.asarray(a.keys.row[pad]) == 0).all()
    assert (np.asarray(a.vals[pad]) == 0).all()
    assert not np.asarray(a.valid_mask()[pad]).any()


@pytest.mark.parametrize("consumer", ["streaming", "stream_service",
                                      "delta_sync"])
def test_narrow_consumers_refuse_wide_shapes(consumer):
    shape = SHAPES[0]
    with pytest.raises(ValueError, match=r"2\*\*31"):
        if consumer == "streaming":
            from repro.core.streaming import StreamingAccumulator
            StreamingAccumulator(shape, cap_budget=8)
        elif consumer == "stream_service":
            from repro.core.stream_service import StreamService
            StreamService().register_tenant("t0", shape, cap_budget=8)
        else:
            from repro.runtime.delta_sync import DeltaFrame, frame_to_coo
            frame_to_coo(DeltaFrame(1, 0, "w", 1 << 31,
                                    np.zeros(1, np.int32),
                                    np.ones(1, np.float32)))


def _op_names(lowered):
    return Counter(line.split("=", 1)[1].split()[0].strip('"')
                   for line in lowered.as_text().splitlines()
                   if " = " in line and "stablehlo." in line)


def test_narrow_sorted_regime_is_staged_with_the_same_ops():
    """The staged ``sorted`` regime is ``spkadd_sorted`` op for op, with
    its plan, accumulate and output stages named."""
    rng = np.random.default_rng(3)
    mats = [S.from_coords(jnp.asarray(rng.integers(0, 32, 24), jnp.int32),
                          jnp.asarray(rng.integers(0, 8, 24), jnp.int32),
                          jnp.asarray(rng.standard_normal(24), jnp.float32),
                          (32, 8), nnz=20) for _ in range(6)]
    force = JR.REGIME_FORCES["sorted"]
    assert E.explain_dispatch(mats, cost_model=force)[1] == "sorted"
    staged = jax.jit(lambda ms: E.spkadd_auto(ms, cost_model=force)).lower(
        mats)
    plain = jax.jit(spkadd_sorted).lower(mats)
    assert _op_names(staged) == _op_names(plain)
    text = staged.as_text()
    for st in ("spkadd.plan", "spkadd.accumulate", "spkadd.output"):
        assert f'obs_stage = "{st}"' in text
    compiled = [Counter(re.findall(r" = \S+ ([a-z][\w-]*)\(",
                                   x.compile().as_text()))
                for x in (staged, plain)]
    assert compiled[0] == compiled[1]
