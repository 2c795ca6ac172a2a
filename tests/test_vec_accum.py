"""Lane-parallel accumulation folds (kernels/vec_accum) vs the oracles.

The contract under test is stronger than numerical agreement: both folds
(the serial row update and the one-hot MXU fold), run through the vec
wrapper, must be **bit-identical** to the pure-jnp reference
(``kernels/ref.py``) *and* to the legacy serial in-tile scatter, on every
stream shape — including
duplicate-heavy, all-sentinel, cancellation, and single-key-repeated
chunks. That is what lets the engine swap the serial scatter for the
vectorized folds without perturbing the canonical ``compress_plan``
contract (DESIGN.md §3.3/§4).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis, or fallback shim

from repro.kernels import ops, ref, vec_accum

FOLDS = ["serial", "onehot"]


def make_stream(rng, m, n, nnz, pad, dup_frac=0.5):
    """(keys, vals) with controlled duplicate fraction + sentinel padding."""
    uniq = rng.choice(m * n, size=min(m * n, max(1, int(nnz * (1 - dup_frac)))),
                      replace=False)
    dups = rng.choice(uniq, size=nnz - len(uniq), replace=True) if \
        nnz > len(uniq) else np.empty((0,), np.int64)
    keys = np.concatenate([uniq, dups]).astype(np.int32)
    rng.shuffle(keys)
    vals = rng.standard_normal(len(keys)).astype(np.float32)
    keys = np.concatenate([keys, np.full(pad, m * n, np.int32)])
    vals = np.concatenate([vals, np.zeros(pad, np.float32)])
    return jnp.asarray(keys), jnp.asarray(vals)


def assert_bitwise(got, want, msg=""):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                  err_msg=msg)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("size", [8, 32, 128])
def test_run_offsets_rank_within_runs(size):
    """Each element's rank inside its run of equal slots (the one-hot
    fold's round index) matches a host-side count, with invalid elements
    breaking runs as sentinels do."""
    rng = np.random.default_rng(size)
    slot = np.sort(rng.integers(0, 7, size=size)).astype(np.int32)
    valid = rng.random(size) > 0.2
    slot = np.where(valid, slot, 99)
    want = np.zeros(size, np.int32)
    for i in range(1, size):
        if valid[i] and valid[i - 1] and slot[i] == slot[i - 1]:
            want[i] = want[i - 1] + 1
    got = jax.jit(vec_accum.run_offsets)(jnp.asarray(slot[None]),
                                         jnp.asarray(valid[None]))
    got = np.asarray(got)[0]
    np.testing.assert_array_equal(got[valid], want[valid])


def test_run_offsets_counts_runs():
    slot = jnp.asarray(np.array([[0, 0, 2, 2, 2, 5, 9, 9]], np.int32))
    valid = jnp.asarray(np.array([[1, 1, 1, 1, 1, 1, 0, 0]], bool))
    rank = np.asarray(jax.jit(vec_accum.run_offsets)(slot, valid))[0]
    np.testing.assert_array_equal(rank[:6], [0, 1, 0, 1, 2, 0])
    assert int(rank[:6].max()) + 1 == 3  # the one-hot fold's round count


@pytest.mark.parametrize("fold", ["spa", "serial", "onehot"])
def test_fold_is_left_associated(fold):
    """Every fold must reproduce the exact left-fold bits — values chosen
    so a tree-shaped sum (a+b)+(c+d) differs in the last ulp from the
    stream fold ((a+b)+c)+d. ``spa`` is the legacy blocked-SPA wrapper."""
    vals = np.array([1e8, 1.0, 1.0, 1.0], np.float32)
    keys = jnp.zeros(4, jnp.int32)
    got = ops.vec_accumulate(keys, jnp.asarray(vals), m=8, n=2, fold=fold,
                             block_rows=8, chunk=4) if fold != "spa" else \
        ops.spa_accumulate(keys, jnp.asarray(vals), m=8, n=2, block_rows=8,
                           chunk=4)
    want = np.float32(0.0)
    for v in vals:
        want = np.float32(want + v)
    assert np.asarray(got)[0, 0] == want


# ---------------------------------------------------------------------------
# bit-exactness of the full folds vs ref.py and vs the serial scatter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fold", FOLDS)
@pytest.mark.parametrize("m,n,nnz,block_rows,chunk", [
    (32, 8, 50, 8, 16),
    (64, 16, 300, 16, 64),
    (128, 4, 100, 32, 128),     # chunk > nnz: padding path
    (56, 12, 200, 8, 32),       # m not a block multiple
    (8, 8, 64, 64, 16),         # block > m
    (16, 16, 400, 8, 32),       # more inputs than slots: long runs
])
def test_vec_accumulate_sweep_bitwise(fold, m, n, nnz, block_rows, chunk):
    rng = np.random.default_rng(hash((m, n, nnz)) % 2**31)
    keys, vals = make_stream(rng, m, n, nnz, pad=13)
    got = ops.vec_accumulate(keys, vals, m=m, n=n, fold=fold,
                             block_rows=min(block_rows, m), chunk=chunk)
    want = ref.spa_accumulate_ref(keys, vals, m=m, n=n)
    serial = ops.spa_accumulate(keys, vals, m=m, n=n,
                                block_rows=min(block_rows, m), chunk=chunk)
    assert_bitwise(got, want, msg=f"{fold} vs ref")
    assert_bitwise(got, serial, msg=f"{fold} vs serial scatter")


@pytest.mark.parametrize("fold", FOLDS)
def test_vec_duplicate_heavy(fold):
    """90% duplicates: long runs of one slot inside a chunk."""
    rng = np.random.default_rng(3)
    keys, vals = make_stream(rng, 16, 8, 400, pad=16, dup_frac=0.9)
    got = ops.vec_accumulate(keys, vals, m=16, n=8, fold=fold,
                             block_rows=8, chunk=64)
    assert_bitwise(got, ref.spa_accumulate_ref(keys, vals, m=16, n=8))


@pytest.mark.parametrize("fold", FOLDS)
def test_vec_all_sentinel(fold):
    keys = jnp.full((64,), 16 * 4, jnp.int32)
    vals = jnp.zeros((64,), jnp.float32)
    got = ops.vec_accumulate(keys, vals, m=16, n=4, fold=fold,
                             block_rows=8, chunk=16)
    assert_bitwise(got, np.zeros((16, 4), np.float32))


@pytest.mark.parametrize("fold", FOLDS)
def test_vec_single_key_repeated_chunks(fold):
    """One key across many chunks: the run spans every chunk boundary, so
    the fold must continue the accumulator's prefix (load-init + overwrite)
    to stay left-associated — the worst case for cross-chunk bit-identity
    and for serial depth (run length == chunk)."""
    rng = np.random.default_rng(11)
    vals = rng.standard_normal(96).astype(np.float32)
    keys = np.full(96, 7, np.int32)
    kj, vj = jnp.asarray(keys), jnp.asarray(vals)
    got = ops.vec_accumulate(kj, vj, m=16, n=4, fold=fold,
                             block_rows=8, chunk=16)
    assert_bitwise(got, ref.spa_accumulate_ref(kj, vj, m=16, n=4))


@pytest.mark.parametrize("fold", FOLDS)
def test_vec_cancellation(fold):
    """a + (-a) per key: totals cancel to exactly +0.0, bitwise equal to
    the scatter's cancellation (the engine keeps cancelled keys
    structurally; the dense value must agree to the bit, sign included)."""
    rng = np.random.default_rng(5)
    k = rng.integers(0, 64, 30).astype(np.int32)
    v = rng.standard_normal(30).astype(np.float32)
    keys = jnp.asarray(np.concatenate([k, k]))
    vals = jnp.asarray(np.concatenate([v, -v]))
    got = ops.vec_accumulate(keys, vals, m=16, n=4, fold=fold,
                             block_rows=8, chunk=16)
    want = ref.spa_accumulate_ref(keys, vals, m=16, n=4)
    assert_bitwise(got, want)
    # cancelled slots must be exactly +0.0 (array_equal treats -0 == +0;
    # nonzero slots may hold legitimate negative fold residues)
    g = np.asarray(got)
    assert not np.signbit(g[g == 0.0]).any()


@pytest.mark.parametrize("fold", FOLDS)
def test_vec_unsorted_stream_allclose(fold):
    """The raw kernel contract: on an arbitrary (unsorted) stream the
    result is numerically correct; the public wrapper pre-sorts, which is
    what upgrades it to bit-exact — both properties hold through
    ops.vec_accumulate."""
    rng = np.random.default_rng(9)
    keys, vals = make_stream(rng, 32, 8, 120, pad=8, dup_frac=0.6)
    got = ops.vec_accumulate(keys, vals, m=32, n=8, fold=fold,
                             block_rows=8, chunk=32)
    want = ref.spa_accumulate_ref(keys, vals, m=32, n=8)
    assert_bitwise(got, want)  # wrapper pre-sorts -> bitwise


def test_vec_auto_fold_selects_by_tile_size():
    """fold="auto": one-hot for small tiles, serial past the boundary —
    both bit-exact, so this only checks the switch doesn't change bits."""
    rng = np.random.default_rng(13)
    keys, vals = make_stream(rng, 64, 8, 200, pad=8)
    want = ref.spa_accumulate_ref(keys, vals, m=64, n=8)
    small = ops.vec_accumulate(keys, vals, m=64, n=8, fold="auto",
                               block_rows=8, chunk=32,
                               onehot_max_block_elems=4096)
    large = ops.vec_accumulate(keys, vals, m=64, n=8, fold="auto",
                               block_rows=8, chunk=32,
                               onehot_max_block_elems=0)
    assert_bitwise(small, want)
    assert_bitwise(large, want)


@settings(max_examples=12, deadline=None)
@given(m=st.integers(4, 48), n=st.integers(1, 10), nnz=st.integers(1, 120),
       dup=st.floats(0.0, 0.95), seed=st.integers(0, 2**16))
def test_property_vec_folds_bitwise_equal_serial(m, n, nnz, dup, seed):
    """Property: for random shapes/duplicate rates, both vec-wrapper folds
    are bit-identical to the serial scatter and the jnp reference."""
    rng = np.random.default_rng(seed)
    nnz = min(nnz, m * n * 2)
    keys, vals = make_stream(rng, m, n, nnz, pad=3, dup_frac=dup)
    want = np.asarray(ref.spa_accumulate_ref(keys, vals, m=m, n=n))
    serial = np.asarray(ops.spa_accumulate(keys, vals, m=m, n=n,
                                           block_rows=8, chunk=32))
    for fold in FOLDS:
        got = np.asarray(ops.vec_accumulate(keys, vals, m=m, n=n, fold=fold,
                                            block_rows=8, chunk=32))
        np.testing.assert_array_equal(got, want, err_msg=f"{fold} vs ref")
        np.testing.assert_array_equal(got, serial,
                                      err_msg=f"{fold} vs serial")


# ---------------------------------------------------------------------------
# serial-store accounting (the perf claim, measurable without a TPU)
# ---------------------------------------------------------------------------

def test_store_counts_serial_per_cell_onehot_none():
    """The serial fold stores once per element of every (part, chunk) cell
    of the row-tiled grid; the one-hot fold never stores serially."""
    rng = np.random.default_rng(2)
    keys, _ = make_stream(rng, 32, 8, 300, pad=20, dup_frac=0.8)
    sc = ops.vec_store_counts(np.asarray(keys), m=32, n=8, block_rows=8,
                              chunk=32)
    assert sc["onehot_fold"] == 0
    assert (sc["parts"], sc["num_chunks"]) == (4, 10)
    assert sc["serial"] == sc["parts"] * sc["num_chunks"] * 32
