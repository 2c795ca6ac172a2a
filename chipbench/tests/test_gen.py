"""The on-device generators: seeded, in range, and Graph500's recursion."""
import jax
import numpy as np
import pytest

from chipbench.drivers.common import seed_key
from chipbench.run import load_module

RMAT = {"a": 0.57, "b": 0.19, "c": 0.19, "d": 0.05}


def _triples(family, seed, step, m=256, n=256, k=4, nnz=512):
    gen = load_module("gen", family)
    key = jax.random.fold_in(seed_key(seed), np.uint32(step))
    params = dict(RMAT, edgefactor=k * nnz // m) if family == "rmat" else {}
    keys, vals = gen.triples(key, m=m, n=n, k=k, nnz=nnz, params=params)
    return np.asarray(keys), np.asarray(vals)


@pytest.mark.parametrize("family", ["er", "rmat"])
def test_same_seed_and_step_same_collection(family):
    k1, v1 = _triples(family, 2**31 + 5, 3)
    k2, v2 = _triples(family, 2**31 + 5, 3)
    k3, v3 = _triples(family, 2**31 + 5, 4)
    k4, _ = _triples(family, 2**33 + 5, 3)
    assert np.array_equal(k1, k2) and np.array_equal(v1, v2)
    assert not np.array_equal(k1, k3) and not np.array_equal(v1, v3)
    assert not np.array_equal(k1, k4)


@pytest.mark.parametrize("family", ["er", "rmat"])
def test_keys_stay_below_mn(family):
    keys, vals = _triples(family, 9, 0, m=128, n=128, k=8, nnz=4096)
    assert keys.dtype == np.int32 and vals.dtype == np.float32
    assert keys.min() >= 0 and keys.max() < 128 * 128


def test_rmat_quadrant_shares_match_abcd():
    gen = load_module("gen", "rmat")
    scale, count = 10, 100_000
    rows, cols = gen.edges(seed_key(1), scale=scale, count=count,
                           a=RMAT["a"], b=RMAT["b"], c=RMAT["c"])
    rows, cols = np.asarray(rows), np.asarray(cols)
    levels = np.arange(scale)[:, None]
    rb, cb = (rows >> levels) & 1, (cols >> levels) & 1
    shares = [np.mean((rb == r) & (cb == c)) for r, c in
              ((0, 0), (0, 1), (1, 0), (1, 1))]
    want = [RMAT[q] for q in "abcd"]
    assert np.allclose(shares, want, atol=0.003), shares


def _numpy_rmat_cf(rng, scale, count):
    """The Graph500 recursion in numpy: each level picks a quadrant."""
    q = rng.choice(4, size=(scale, count), p=[RMAT[x] for x in "abcd"])
    w = (1 << np.arange(scale))[:, None]
    rows = ((q >= 2) * w).sum(0)
    cols = ((q % 2 == 1) * w).sum(0)
    return count / len(np.unique(cols * (1 << scale) + rows))


def test_rmat_compression_factor_matches_numpy():
    scale, k, nnz = 10, 16, 4096
    m = 1 << scale
    keys, _ = _triples("rmat", 77, 0, m=m, n=m, k=k, nnz=nnz)
    cf = keys.size / len(np.unique(keys))
    want = _numpy_rmat_cf(np.random.default_rng(0), scale, k * nnz)
    assert cf > 1.5  # skewed: far above ER's ~1.03 at this fill
    assert abs(cf - want) / want < 0.03, (cf, want)


def test_rmat_holds_to_its_edgefactor():
    gen = load_module("gen", "rmat")
    with pytest.raises(ValueError, match="edgefactor"):
        gen.triples(seed_key(1), m=64, n=64, k=8, nnz=256,
                    params=dict(RMAT, edgefactor=16))


def test_rmat_scramble_is_a_bijection():
    import jax.numpy as jnp
    gen = load_module("gen", "rmat")
    for scale, (v0, v1) in [(6, (1, 2)), (13, (0xDEADBEEF, 12345))]:
        v = gen.scramble(jnp.arange(1 << scale), scale, jnp.uint32(v0),
                         jnp.uint32(v1))
        out = np.asarray(v)
        assert sorted(out.tolist()) == list(range(1 << scale))
        assert not np.array_equal(out, np.arange(1 << scale))
