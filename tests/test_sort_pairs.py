"""Key-payload sorts (``sparse.stable_sort_pairs``) in place of an argsort
followed by gathers through the permutation.

Three contracts under test:

1. **Bit-identity.** ``stable_sort_pairs(keys, *p)`` equals
   ``argsort`` + ``take_along_axis`` bit for bit, and every engine regime,
   ``from_dense``, ``two_way_add`` and SUMMA's C are bitwise what the same
   code gives with the argsort-then-gather idiom patched back in.
2. **Counting.** Each canonical plan is one payload sort
   (``sparse.payload_sorts``), and ``sort_calls()`` per regime is what the
   one-sort matrix (SPKJ201) expects.
3. **No permutation gathers.** No ``gather`` in the traced program takes
   its indices from a ``sort``'s output.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.analysis import jaxpr_rules as JR
from repro.core import engine as E
from repro.core import sparse as S
from repro.kernels import ops as kops
from repro.obs import metrics

_alg = importlib.import_module("repro.core.spkadd")

REGIMES = list(E._CANONICAL)


def argsort_gather(keys, *payloads, axis=-1):
    """The idiom ``stable_sort_pairs`` replaces: argsort, then one gather
    per array through the permutation."""
    order = S.stable_argsort(keys, axis=axis)
    return tuple(jnp.take_along_axis(x, order, axis=axis)
                 for x in (keys, *payloads))


@pytest.fixture
def argsort_idiom(monkeypatch):
    """Every caller of ``stable_sort_pairs`` runs the argsort-then-gather
    idiom instead: the engine as it was before the payload sort."""
    for mod, name in [(S, "stable_sort_pairs"), (E, "stable_sort_pairs"),
                      (_alg, "stable_sort_pairs"),
                      (kops, "_stable_sort_pairs")]:
        monkeypatch.setattr(mod, name, argsort_gather)


def bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def assert_same_bits(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(bits(g), bits(w))


def duplicate_heavy_collection(seed, k, m, n, cap):
    """k matrices whose keys come from a few columns, so most keys repeat
    across the collection, with signed zeros among the values and sentinel
    padding at each tail."""
    rng = np.random.default_rng(seed)
    sent = S.sentinel_key((m, n))
    mats = []
    for _ in range(k):
        nnz = int(rng.integers(cap // 2, cap + 1))
        keys = np.sort(rng.choice(2 * m, nnz, replace=False)).astype(np.int32)
        vals = rng.standard_normal(nnz).astype(np.float32)
        vals[rng.random(nnz) < 0.1] = -0.0
        keys = np.concatenate([keys, np.full(cap - nnz, sent, np.int32)])
        vals = np.concatenate([vals, np.zeros(cap - nnz, np.float32)])
        mats.append(S.PaddedCOO(jnp.asarray(keys), jnp.asarray(vals),
                                jnp.asarray(nnz, jnp.int32), (m, n)))
    return mats


# ---------------------------------------------------------------------------
# 1. bit-identity
# ---------------------------------------------------------------------------

_PAIR_CASES = ["duplicate_heavy", "sentinel_padding", "all_sentinel",
               "batched"]


def _pair_case(name):
    rng = np.random.default_rng(_PAIR_CASES.index(name))
    sent = 64
    if name == "duplicate_heavy":
        keys = rng.integers(0, 4, 257)
    elif name == "sentinel_padding":
        keys = np.where(rng.random(200) < 0.3, sent, rng.integers(0, 16, 200))
    elif name == "all_sentinel":
        keys = np.full(96, sent)
    else:  # batched (B, cap)
        keys = np.where(rng.random((3, 128)) < 0.2, sent,
                        rng.integers(0, 8, (3, 128)))
    keys = jnp.asarray(keys, jnp.int32)
    vals = rng.standard_normal(keys.shape).astype(np.float32)
    vals[..., ::7] = -0.0
    vals[..., 3::11] = np.nan
    return keys, jnp.asarray(vals)


@pytest.mark.parametrize("case", _PAIR_CASES)
def test_stable_sort_pairs_matches_argsort_gather(case):
    keys, vals = _pair_case(case)
    assert_same_bits(S.stable_sort_pairs(keys, vals),
                     argsort_gather(keys, vals))


@pytest.mark.parametrize("form", ["vmapped", "three_payloads"])
def test_stable_sort_pairs_vmapped_and_payload_count(form):
    keys, vals = _pair_case("batched")
    if form == "vmapped":
        assert_same_bits(jax.vmap(S.stable_sort_pairs)(keys, vals),
                         jax.vmap(argsort_gather)(keys, vals))
    else:
        ids = jnp.broadcast_to(jnp.arange(keys.shape[-1], dtype=jnp.int32),
                               keys.shape)
        flags = keys == 64
        assert_same_bits(S.stable_sort_pairs(keys, vals, ids, flags),
                         argsort_gather(keys, vals, ids, flags))


def _regime_outputs(forced, batched):
    """The regime's result on a duplicate-heavy collection, single or as a
    stacked batch of three. Traced afresh each call (a new function), so a
    patched sort idiom cannot be served from a cached trace."""
    if batched:
        colls = [duplicate_heavy_collection(40 + b, 5, 16, 8, 24)
                 for b in range(3)]
        stacked = E.stack_collections(colls)
        return jax.jit(lambda s: E.spkadd_batched(
            s, algorithm=forced))(stacked)
    mats = duplicate_heavy_collection(41, 3 if forced == "tree" else 6,
                                      16, 8, 24)
    return jax.jit(lambda ms: E._CANONICAL[forced](ms))(mats)


@pytest.mark.parametrize("batched", [False, True], ids=["single", "batched"])
@pytest.mark.parametrize("forced", REGIMES)
def test_regime_bitwise_equal_to_argsort_gather(forced, batched, request):
    got = _regime_outputs(forced, batched)
    request.getfixturevalue("argsort_idiom")
    want = _regime_outputs(forced, batched)
    assert_same_bits((got.keys, got.vals, got.nnz),
                     (want.keys, want.vals, want.nnz))


@pytest.mark.parametrize("fn", ["from_dense", "two_way_add"])
def test_sparsify_and_two_way_add_bitwise_equal_to_argsort_gather(
        fn, request):
    rng = np.random.default_rng(5)
    dense = rng.standard_normal((16, 8)).astype(np.float32)
    dense[rng.random(dense.shape) < 0.5] = 0.0
    mats = duplicate_heavy_collection(42, 2, 16, 8, 24)

    def run():
        if fn == "from_dense":
            return jax.jit(lambda d: S.from_dense(d, cap=96))(dense)
        return jax.jit(_alg.two_way_add)(*mats)

    got = run()
    request.getfixturevalue("argsort_idiom")
    want = run()
    assert_same_bits((got.keys, got.vals, got.nnz),
                     (want.keys, want.vals, want.nnz))


def test_summa_c_bitwise_equal_to_argsort_gather(multidevice):
    """SUMMA on a 2x2 mesh: its two stage partials overlap almost
    everywhere, so the k = 2 reduction adds duplicates at most keys."""
    multidevice(r"""
import importlib
import jax.numpy as jnp, numpy as np
from repro.compat import make_mesh
from repro.core import engine as E, sparse as S
from repro.core.spgemm import spgemm_summa
from repro.kernels import ops as kops
_alg = importlib.import_module("repro.core.spkadd")
rng = np.random.default_rng(11)
A = rng.standard_normal((32, 24)).astype(np.float32)
B = rng.standard_normal((24, 16)).astype(np.float32)
A[rng.random(A.shape) < 0.3] = 0.0
mesh = make_mesh((2, 2), ('data', 'model'))
got = np.asarray(spgemm_summa(jnp.asarray(A), jnp.asarray(B), mesh))

def argsort_gather(keys, *payloads, axis=-1):
    order = S.stable_argsort(keys, axis=axis)
    return tuple(jnp.take_along_axis(x, order, axis=axis)
                 for x in (keys, *payloads))
for mod, name in [(S, "stable_sort_pairs"), (E, "stable_sort_pairs"),
                  (_alg, "stable_sort_pairs"), (kops, "_stable_sort_pairs")]:
    setattr(mod, name, argsort_gather)
want = np.asarray(spgemm_summa(jnp.asarray(A), jnp.asarray(B), mesh))
np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
np.testing.assert_allclose(got, A @ B, rtol=1e-4, atol=1e-4)
print('summa bitwise ok')
""", n_devices=4)


# ---------------------------------------------------------------------------
# 2. counting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("forced", REGIMES)
def test_payload_sorts_one_per_plan(forced):
    k = 3
    mats = JR._collection(60, k, 16, 8, 24)
    payload = metrics.counter(S.PAYLOAD_SORT_COUNTER_NAME)
    before_sorts, before_payload = S.sort_calls(), payload.value
    E.spkadd_auto(mats, cost_model=dict(JR.REGIME_FORCES[forced]))
    sorts = S.sort_calls() - before_sorts
    assert sorts == JR.expected_sorts(forced, k)
    assert payload.value - before_payload == sorts


# ---------------------------------------------------------------------------
# 3. no gather indexed by a sort's output
# ---------------------------------------------------------------------------

#: primitives through which an index array stays the same index array:
#: jnp's negative-index wrap (``add``, ``select_n``'s cases), casts, and the
#: reshapes and concatenations that build a gather's index operand
_INDEX_PRESERVING = {"add", "sub", "mul", "div", "rem", "neg", "max", "min",
                     "clamp", "convert_element_type", "broadcast_in_dim",
                     "reshape", "squeeze", "expand_dims", "concatenate",
                     "slice", "dynamic_slice", "transpose", "copy"}


def _subjaxprs(params):
    for v in params.values():
        for item in v if isinstance(v, (list, tuple)) else [v]:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def sort_indexed_gathers(jaxpr, from_sort=frozenset()):
    """``(gathers whose indices derive from a sort's output, vars so
    derived)`` in ``jaxpr``, whose invars ``from_sort`` already are. A
    sub-jaxpr whose invars and outvars line up with its equation's (jit,
    custom calls) carries the derivation through; others are checked on
    their own."""
    derived = set(from_sort)

    def is_derived(v):
        return not hasattr(v, "val") and v in derived

    hits = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather" and is_derived(eqn.invars[1]):
            hits.append(eqn)
        if (name == "sort"
                or (name == "select_n"
                    and any(map(is_derived, eqn.invars[1:])))
                or (name in _INDEX_PRESERVING
                    and any(map(is_derived, eqn.invars)))):
            derived.update(eqn.outvars)
        for sub in _subjaxprs(eqn.params):
            lined_up = len(sub.invars) == len(eqn.invars)
            inner = frozenset(s for s, v in zip(sub.invars, eqn.invars)
                              if lined_up and is_derived(v))
            sub_hits, sub_derived = sort_indexed_gathers(sub, inner)
            hits += sub_hits
            if len(sub.outvars) == len(eqn.outvars):
                derived.update(o for o, s in zip(eqn.outvars, sub.outvars)
                               if not hasattr(s, "val") and s in sub_derived)
    return hits, derived


def _traced(entry):
    """The closed jaxpr of one public entry point on a tiny input."""
    mats = JR._collection(70, 3, 16, 4, 8)
    if entry.startswith("spkadd_auto["):
        force = dict(JR.REGIME_FORCES[entry[12:-1]])
        return jax.make_jaxpr(
            lambda ms: E.spkadd_auto(ms, cost_model=force))(mats)
    if entry.startswith("spkadd_batched["):
        force = dict(JR.REGIME_FORCES[entry[15:-1]])
        stacked = E.stack_collections(
            [JR._collection(71 + b, 3, 16, 4, 8) for b in range(2)])
        return jax.make_jaxpr(
            lambda s: E.spkadd_batched(s, cost_model=force))(stacked)
    if entry == "from_dense":
        return jax.make_jaxpr(lambda d: S.from_dense(d, cap=24))(
            jnp.zeros((16, 4), jnp.float32))
    return jax.make_jaxpr(_alg.two_way_add)(mats[0], mats[1])


ENTRIES = ([f"spkadd_auto[{r}]" for r in REGIMES]
           + [f"spkadd_batched[{r}]" for r in REGIMES]
           + ["from_dense", "two_way_add"])


@pytest.mark.parametrize("entry", ENTRIES)
def test_no_gather_indexed_by_a_sort(entry):
    hits, _ = sort_indexed_gathers(_traced(entry).jaxpr)
    assert not hits, f"{len(hits)} gather(s) index by a sort's output"


def test_sort_indexed_gather_check_sees_the_argsort_idiom(argsort_idiom):
    """The check is not blind: with the argsort-then-gather idiom patched
    back in, it finds the two gathers of every plan."""
    hits, _ = sort_indexed_gathers(_traced("spkadd_auto[vec]").jaxpr)
    assert len(hits) == 2
