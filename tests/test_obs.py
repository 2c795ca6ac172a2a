"""Observability layer (repro/obs): spans, stages, metrics, ledger,
regression gate, and the engine/streaming/allreduce instrumentation
contracts.

The three hard promises under test:

1. **Disabled == invisible.** With ``SPKADD_OBS`` off, instrumented paths
   are bit-identical and lower to byte-identical HLO (no added jit-traced
   ops) — spans live on the host at trace/launch boundaries only.
2. **The ledger has memory.** BENCH artifacts append into a keyed ledger
   (dedup by (commit, backend, suite, geometry)), and the regression gate
   trips on a synthetic regression but not on a flat trajectory.
3. **Device work is named.** Every sort, gather, scatter, custom call and
   call of a lowered engine or SUMMA program carries its stage.
"""
import json
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import engine as E
from repro.core import sparse as S
from repro.core.streaming import StreamingAccumulator
from repro.obs import ledger, metrics, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)  # for `benchmarks.common` (namespace package)

FORCE_VEC = {"tree_max_k": 0, "spa_max_accum_elems": 1.0,
             "vec_min_density": 0.0, "vec_max_accum_elems": float(1 << 40)}


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts with spans cleared and the env override released;
    metric *objects* persist (modules cache handles) but that's exactly the
    registry contract — tests assert deltas, not absolutes."""
    trace.set_enabled(None)
    trace.clear()
    yield
    trace.set_enabled(None)
    trace.clear()


def random_collection(seed, k, m, n, nnz):
    rng = np.random.default_rng(seed)
    mats = []
    for _ in range(k):
        d = np.zeros((m, n), np.float32)
        idx = rng.choice(m * n, min(nnz, m * n), replace=False)
        d.flat[idx] = rng.standard_normal(len(idx))
        mats.append(S.from_dense(jnp.asarray(d), cap=nnz))
    return mats


# ---------------------------------------------------------------------------
# trace spans
# ---------------------------------------------------------------------------

def test_span_nesting_and_attribute_capture():
    trace.set_enabled(True)
    with obs.span("outer", a=1) as sp:
        sp.set_attr("b", "two")
        with obs.span("inner", c=3.5):
            pass
    recs = trace.spans()
    assert [r["name"] for r in recs] == ["inner", "outer"]  # finish order
    inner, outer = recs
    assert inner["depth"] == 1 and inner["parent"] == "outer"
    assert outer["depth"] == 0 and outer["parent"] is None
    assert outer["attrs"] == {"a": 1, "b": "two"}
    assert inner["attrs"] == {"c": 3.5}
    assert outer["dur_ns"] >= inner["dur_ns"] >= 0


def test_span_disabled_records_nothing_and_is_shared_noop():
    trace.set_enabled(False)
    with obs.span("x", a=1) as sp:
        sp.set_attr("b", 2)  # must not raise
        with obs.span("y") as sp2:
            assert sp2 is sp  # the shared null instance
    assert trace.spans() == []


def test_span_env_switch(monkeypatch):
    trace.set_enabled(None)  # defer to env
    monkeypatch.delenv(trace.OBS_ENV, raising=False)
    assert not obs.enabled()
    monkeypatch.setenv(trace.OBS_ENV, "0")
    assert not obs.enabled()
    monkeypatch.setenv(trace.OBS_ENV, "1")
    assert obs.enabled()


def test_span_jsonl_round_trip(tmp_path):
    trace.set_enabled(True)
    with obs.span("a", k=4, alg="vec", arr=np.int32(7)):
        pass
    path = str(tmp_path / "sub" / "trace.jsonl")  # dir must be created
    n = trace.export_jsonl(path)
    assert n == 1
    back = trace.read_jsonl(path)
    assert len(back) == 1
    r = back[0]
    assert set(r) == {"name", "t_ns", "dur_ns", "depth", "parent", "attrs"}
    assert r["name"] == "a"
    assert r["attrs"] == {"k": 4, "alg": "vec", "arr": 7}  # np scalar -> int


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_snapshot_reset_isolation():
    c = metrics.counter("test_obs.c")
    g = metrics.gauge("test_obs.g")
    h = metrics.histogram("test_obs.h")
    metrics.reset("test_obs.")
    c.inc()
    c.inc(2)
    g.set(7.5)
    h.observe(3)
    h.observe(5)
    snap = metrics.snapshot("test_obs.")
    assert snap["test_obs.c"] == {"type": "counter", "value": 3}
    assert snap["test_obs.g"] == {"type": "gauge", "value": 7.5}
    assert snap["test_obs.h"] == {"type": "histogram", "count": 2,
                                  "total": 8, "min": 3, "max": 5}
    # snapshot is a copy: later updates don't mutate it
    c.inc(10)
    assert snap["test_obs.c"]["value"] == 3
    # prefix reset zeroes values but keeps handles registered + live
    metrics.reset("test_obs.")
    assert c.value == 0 and metrics.counter("test_obs.c") is c
    c.inc()
    assert metrics.snapshot("test_obs.")["test_obs.c"]["value"] == 1


def test_metric_kind_collision_raises():
    metrics.counter("test_obs.kind")
    with pytest.raises(TypeError):
        metrics.gauge("test_obs.kind")


def test_sort_calls_backed_by_registry():
    """Satellite: the sort pin migrated onto the registry — the back-compat
    alias, the named counter, and the delta discipline all agree."""
    before = S.sort_calls()
    assert before == metrics.counter(S.SORT_COUNTER_NAME).value
    S.stable_argsort(jnp.asarray([3, 1, 2], jnp.int32))
    assert S.sort_calls() - before == 1
    assert metrics.counter(S.SORT_COUNTER_NAME).value == before + 1
    # the exactly-one-sort engine pin still holds through the registry
    mats = random_collection(13, 8, 48, 8, 36)
    before = S.sort_calls()
    E.spkadd_auto(mats, cost_model=FORCE_VEC)
    assert S.sort_calls() - before == 1
    # ...and survives a registry reset (handle stays registered)
    metrics.reset(S.SORT_COUNTER_NAME)
    before = S.sort_calls()
    E.spkadd_auto(mats, cost_model=FORCE_VEC)
    assert S.sort_calls() - before == 1


# ---------------------------------------------------------------------------
# disabled path: bit-identical, no added jit-traced ops
# ---------------------------------------------------------------------------

def test_obs_disabled_and_enabled_lower_to_identical_hlo():
    """The acceptance pin: observability must never change the lowered
    program — spans are host-side, so enabled and disabled HLO are
    byte-identical (op-count equality is implied by text equality)."""
    mats = random_collection(21, 6, 32, 8, 24)

    def lower_text():
        return jax.jit(
            lambda ms: E.spkadd_auto(ms, cost_model=FORCE_VEC)
        ).lower(mats).as_text()

    trace.set_enabled(False)
    off = lower_text()
    trace.set_enabled(True)
    on = lower_text()
    assert on == off


def test_obs_enabled_outputs_bit_identical():
    mats = random_collection(22, 6, 32, 8, 24)
    trace.set_enabled(False)
    a = E.spkadd_auto(mats, cost_model=FORCE_VEC)
    trace.set_enabled(True)
    b = E.spkadd_auto(mats, cost_model=FORCE_VEC)
    np.testing.assert_array_equal(np.asarray(a.keys), np.asarray(b.keys))
    np.testing.assert_array_equal(np.asarray(a.vals), np.asarray(b.vals))
    assert int(a.nnz) == int(b.nnz)


# ---------------------------------------------------------------------------
# stages: device work named in the lowered program
# ---------------------------------------------------------------------------

#: cost models that force each canonical regime on ``random_collection``'s
#: 6 x 24 inputs in a 32 x 8 key space
FORCE = {
    "tree": {"tree_max_k": 64},
    "sorted": {"tree_max_k": 0, "spa_max_accum_elems": 0.0,
               "hash_min_total_nnz": 1e18, "vec_max_accum_elems": 0.0,
               "blocked_spa_max_accum_elems": 0.0},
    "spa": {"tree_max_k": 0, "spa_max_accum_elems": 1e18,
            "spa_min_density": 0.0},
    "vec": FORCE_VEC,
    "blocked_spa": {"tree_max_k": 0, "spa_max_accum_elems": 1.0,
                    "hash_min_total_nnz": 1e18, "vec_max_accum_elems": 0.0,
                    "blocked_spa_min_density": 0.0,
                    "blocked_spa_max_accum_elems": 1e18},
    "hash": {"tree_max_k": 0, "spa_max_accum_elems": 1.0,
             "hash_min_total_nnz": 0.0, "hash_max_compression": 1e18},
}

#: the regime with no separate phases runs wholly as ``spkadd.accumulate``
REGIME_STAGES = {
    "tree": {"spkadd.accumulate"},
    **{r: {"spkadd.plan", "spkadd.accumulate", "spkadd.output"}
       for r in ("sorted", "spa", "vec", "blocked_spa", "hash")},
}

CHECKED_OPS = ("stablehlo.sort", "stablehlo.gather", "stablehlo.scatter",
               "stablehlo.custom_call", "func.call")


def _stage_attr(op):
    attrs = op.attributes
    if "mhlo.frontend_attributes" not in attrs:
        return None
    fa = attrs["mhlo.frontend_attributes"]
    return str(fa[trace.STAGE_ATTR]).strip('"') \
        if trace.STAGE_ATTR in fa else None


def _nested_ops(op):
    for region in op.regions:
        for block in region.blocks:
            for o in block.operations:
                yield o.operation
                yield from _nested_ops(o.operation)


def module_stages(lowered):
    """(every stage path in a lowered module, the checked ops that carry
    none). A ``func.call`` that jax emits for a cached primitive lowering
    (the cumsum's) has no attributes of its own; XLA inlines it, so it
    takes the one stage its callee's ops carry."""
    module = lowered.compiler_ir("stablehlo")
    funcs = {str(f.attributes["sym_name"]).strip('"'): f
             for f in module.body.operations}
    seen, unstaged = set(), []
    for f in funcs.values():
        for op in _nested_ops(f):
            path = _stage_attr(op)
            if path is not None:
                seen.add(path)
            if op.name not in CHECKED_OPS:
                continue
            if path is None and op.name == "func.call":
                callee = str(op.attributes["callee"]).lstrip("@")
                inner = {p for o in _nested_ops(funcs[callee])
                         if (p := _stage_attr(o)) is not None}
                path = inner.pop() if len(inner) == 1 else None
            if path is None:
                unstaged.append(str(op)[:120])
    return seen, unstaged


@pytest.mark.parametrize("regime", sorted(FORCE))
def test_every_regime_names_its_device_work(regime):
    """Every sort, gather, scatter, custom call and call of the lowered
    engine program carries a ``spkadd.`` stage, and each of the regime's
    stages appears."""
    mats = random_collection(21, 6, 32, 8, 24)
    cm = FORCE[regime]
    assert E.explain_dispatch(mats, cost_model=cm)[1] == regime
    lowered = jax.jit(
        lambda ms: E.spkadd_auto(ms, cost_model=cm)).lower(mats)
    seen, unstaged = module_stages(lowered)
    assert not unstaged
    assert all(p.startswith("spkadd.") for p in seen)
    assert REGIME_STAGES[regime] <= seen


#: the stage that makes a result's keys and nnz: the plan's in the regimes
#: that keep the plan's key layout, the compaction's in ``hash``
KEYS_STAGE = {"sorted": "spkadd.plan", "spa": "spkadd.plan",
              "vec": "spkadd.plan", "blocked_spa": "spkadd.plan",
              "hash": "spkadd.output"}


@pytest.mark.parametrize("regime", sorted(KEYS_STAGE))
def test_result_keys_keep_the_stage_that_made_them(regime):
    """The last op of each result leaf is named by the stage that made the
    leaf, so an op XLA fuses under it (its root) is not moved to another
    stage: keys and nnz by ``KEYS_STAGE``, values by ``spkadd.output``."""
    mats = random_collection(21, 6, 32, 8, 24)
    cm = FORCE[regime]
    module = jax.jit(lambda ms: E.spkadd_auto(ms, cost_model=cm)).lower(
        mats).compiler_ir("stablehlo")
    main = next(f for f in module.body.operations
                if str(f.attributes["sym_name"]).strip('"') == "main")
    ret = list(main.regions[0].blocks[0].operations)[-1]
    keys, vals, nnz = (_stage_attr(v.owner) for v in ret.operands)
    assert (keys, vals, nnz) == (KEYS_STAGE[regime], "spkadd.output",
                                 KEYS_STAGE[regime])


def test_summa_names_its_stages_and_nests_the_engine(multidevice):
    code = """
import json, re
import jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import spgemm
mesh = make_mesh((2, 2), ("data", "model"))
a = jnp.zeros((16, 16), jnp.float32)
text = jax.jit(lambda a, b: spgemm.spgemm_summa(a, b, mesh=mesh)).lower(
    a, a).as_text()
print(json.dumps(sorted(set(re.findall(r'obs_stage = "([^"]*)"', text)))))
"""
    seen = set(json.loads(multidevice(code, n_devices=4).splitlines()[-1]))
    assert {"summa.gather", "summa.multiply", "summa.from_dense",
            "summa.reduce"} <= seen
    assert any(p.startswith("summa.reduce/spkadd.") for p in seen)


def test_stage_rejects_unknown_names_and_nests_paths():
    with pytest.raises(ValueError, match="unknown stage"):
        with obs.stage("spkadd.sort"):
            pass

    def f(x):
        with obs.stage("summa.reduce"):
            y = x + 1
            with obs.stage("spkadd.plan"):
                y = y * 2
        return y - 3

    text = jax.jit(f).lower(jnp.ones(4)).as_text()
    lines = {op: line for line in text.splitlines()
             for op in ("add", "multiply", "subtract")
             if f"stablehlo.{op} " in line}
    assert 'obs_stage = "summa.reduce"}' in lines["add"]
    assert 'obs_stage = "summa.reduce/spkadd.plan"' in lines["multiply"]
    assert "obs_stage" not in lines["subtract"]


# ---------------------------------------------------------------------------
# instrumented paths emit the promised spans/counters
# ---------------------------------------------------------------------------

def test_engine_dispatch_span_and_counter():
    """The dispatch decision is a span; the launch geometry is on the
    partition gauges (device work is named by stages, not spans)."""
    from repro.kernels import ops as kops

    trace.set_enabled(True)
    mats = random_collection(23, 6, 32, 8, 24)
    geom = kops.partitioned_launch_geometry(6 * 24, m=32, n=8)
    keys = ("parts", "part_elems", "chunk", "num_chunks")
    for key in keys:
        metrics.gauge(f"kernels.partition.{key}").set(-1)
    before = metrics.counter("engine.dispatch.vec").value
    launches = metrics.counter("engine.partitioned.launches").value
    E.spkadd_auto(mats, cost_model=FORCE_VEC)
    assert metrics.counter("engine.dispatch.vec").value == before + 1
    assert metrics.counter("engine.partitioned.launches").value \
        == launches + 1
    autos = [r for r in trace.spans() if r["name"] == "engine.spkadd_auto"]
    assert autos and autos[-1]["attrs"]["selected"] == "vec"
    assert autos[-1]["attrs"]["k"] == 6
    for key in keys:
        assert metrics.gauge(f"kernels.partition.{key}").value \
            == getattr(geom, key)
    assert not [r for r in trace.spans() if r["parent"] == "engine.spkadd_auto"]


def test_batched_dispatch_span_reports_requested_and_effective():
    """Satellite: explain_batched_dispatch routes through a span, so a
    silent downgrade would be visible in exported JSONL."""
    trace.set_enabled(True)
    colls = [random_collection(40 + b, 4, 32, 8, 16) for b in range(2)]
    stacked = E.stack_collections(colls)
    _, requested, effective = E.explain_batched_dispatch(
        stacked, cost_model=FORCE_VEC)
    recs = [r for r in trace.spans() if r["name"] == "engine.batched_dispatch"]
    assert recs
    attrs = recs[-1]["attrs"]
    assert attrs["requested"] == requested == "vec"
    assert attrs["effective"] == effective == "vec"
    assert attrs["batch"] == 2


def test_ragged_bucket_occupancy_histogram():
    trace.set_enabled(True)
    h = metrics.histogram("engine.ragged.bucket_occupancy")
    c0, t0 = h.count, h.total
    colls = [random_collection(50, 4, 32, 8, 24),
             random_collection(51, 4, 32, 8, 17),  # same pow2 bucket as [0]
             random_collection(52, 3, 32, 8, 24)]  # different k
    E.spkadd_batched_ragged(colls, algorithm="spa")
    assert h.count - c0 == 2           # two buckets
    assert h.total - t0 == 3           # three collections total
    recs = [r for r in trace.spans()
            if r["name"] == "engine.spkadd_batched_ragged"]
    assert recs and recs[-1]["attrs"]["buckets"] == 2


def test_streaming_flush_spans_and_sizes():
    trace.set_enabled(True)
    c = metrics.counter("streaming.flushes")
    h = metrics.histogram("streaming.flush_size")
    c0, h0 = c.value, h.count
    acc = StreamingAccumulator((16, 8), batch_k=2, cap_budget=64,
                               algorithm="spa")
    for i in range(4):  # two flushes of 2
        acc.push(random_collection(60 + i, 1, 16, 8, 8)[0])
    assert c.value - c0 == 2 and h.count - h0 == 2
    recs = [r for r in trace.spans() if r["name"] == "streaming.flush"]
    assert len(recs) >= 2
    assert recs[-1]["attrs"]["buffered"] == 2
    assert recs[-1]["attrs"]["algorithm"] == "spa"


def test_allreduce_modeled_bytes_counter():
    from repro.core.allreduce import modeled_schedule_bytes
    assert modeled_schedule_bytes("gather_kway", p=8, s=64) == 8 * 64 * 8
    assert modeled_schedule_bytes("tree_2way", p=8, s=64) == 7 * 64 * 8
    assert modeled_schedule_bytes("ring_2way", p=8, s=64) == 7 * 64 * 8


# ---------------------------------------------------------------------------
# perf-history ledger + regression gate
# ---------------------------------------------------------------------------

def payload(suite, names_vals, backend="cpu"):
    return {"meta": {"suite": suite, "backend": backend,
                     "timestamp": "2026-08-08T00:00:00Z"},
            "records": [{"name": n, "value": v, "derived": ""}
                        for n, v in names_vals]}


def test_ledger_append_and_dedup_by_key(tmp_path):
    hist = str(tmp_path / "history")
    ledger.append_bench(hist, payload("s1", [("io/x/onepass_loads", 4)]),
                        commit="aaa")
    ledger.append_bench(hist, payload("s1", [("io/x/onepass_loads", 5)]),
                        commit="bbb")
    assert len(ledger.load(hist)) == 2
    # same key (commit, backend, suite, geometry) -> replace, not duplicate
    ledger.append_bench(hist, payload("s1", [("io/x/onepass_loads", 6)]),
                        commit="bbb")
    entries = ledger.load(hist)
    assert len(entries) == 2
    assert entries[-1]["records"][0]["value"] == 6
    # a different geometry under the same commit is a distinct key
    ledger.append_bench(hist, payload("s1", [("io/x/onepass_loads", 9)]),
                        commit="bbb", geometry="tpu-v4")
    assert len(ledger.load(hist)) == 3


def test_ledger_file_round_trip(tmp_path):
    hist = str(tmp_path / "history")
    bench = tmp_path / "BENCH_x.json"
    bench.write_text(json.dumps(payload("sx", [("smoke/serial_stores", 128)])))
    entry = ledger.append_bench_file(hist, str(bench), commit="ccc")
    assert entry["key"]["suite"] == "sx"
    loaded = ledger.load(hist)
    assert loaded == [entry]


def test_regression_gate_pass_and_fail_on_synthetic_history(tmp_path):
    hist = str(tmp_path / "history")
    for i, commit in enumerate(["c1", "c2", "c3"]):
        ledger.append_bench(
            hist, payload("spkadd_io_smoke", [("io/a/onepass_loads", 10),
                                              ("untracked/metric", 100 * i)]),
            commit=commit)
    # flat trajectory (and a wildly-moving untracked series): clean
    assert ledger.check_regressions(ledger.load(hist)) == []
    # within tolerance: clean
    ledger.append_bench(hist, payload("spkadd_io_smoke",
                                      [("io/a/onepass_loads", 10.4)]),
                        commit="c4")
    assert ledger.check_regressions(ledger.load(hist), rel_tol=0.05) == []
    # injected synthetic regression: the gate trips with a readable message
    ledger.append_bench(hist, payload("spkadd_io_smoke",
                                      [("io/a/onepass_loads", 20)]),
                        commit="c5")
    problems = ledger.check_regressions(ledger.load(hist), rel_tol=0.05)
    assert len(problems) == 1
    assert "io/a/onepass_loads" in problems[0] and "c5" in problems[0]
    # improvements never trip (lower is better)
    ledger.append_bench(hist, payload("spkadd_io_smoke",
                                      [("io/a/onepass_loads", 3)]),
                        commit="c6")
    assert ledger.check_regressions(ledger.load(hist), rel_tol=0.05) == []


def test_tracked_oracle_patterns():
    names = ["io/two_parts/onepass_loads", "smoke/serial_stores",
             "allreduce/dense/coll_bytes",
             "allreduce_4x2/topk0.05/gather_kway/coll_bytes",
             "table_er/auto/k=4/d=4", "io/two_parts/read_amplification"]
    tracked = ledger.tracked_names(names)
    assert "io/two_parts/onepass_loads" in tracked
    assert "smoke/serial_stores" in tracked
    assert "allreduce/dense/coll_bytes" in tracked
    assert "allreduce_4x2/topk0.05/gather_kway/coll_bytes" in tracked
    assert "table_er/auto/k=4/d=4" not in tracked
    assert "io/two_parts/read_amplification" not in tracked


# ---------------------------------------------------------------------------
# benchmarks/common.py artifact hygiene (satellite)
# ---------------------------------------------------------------------------

def test_write_json_creates_dir_and_resets_records(tmp_path, capsys):
    from benchmarks import common as bcommon
    bcommon.reset_records()
    bcommon.emit("a/b", 1.0, "first run")
    path1 = str(tmp_path / "deep" / "nested" / "BENCH_one.json")
    bcommon.write_json(path1, suite="one")
    assert os.path.exists(path1)
    with open(path1) as f:
        one = json.load(f)
    assert [r["name"] for r in one["records"]] == ["a/b"]
    assert one["meta"]["suite"] == "one"
    # second invocation in the same process: no cross-contamination
    bcommon.emit("c/d", 2.0, "second run")
    path2 = str(tmp_path / "BENCH_two.json")
    bcommon.write_json(path2, suite="two")
    with open(path2) as f:
        two = json.load(f)
    assert [r["name"] for r in two["records"]] == ["c/d"]
    assert bcommon.RECORDS == []
