"""The reduction from a device trace to per-layer numbers."""
import os

import pytest

from chipbench import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "er_hash.xplane.pb")

SORT = ("%sort.0 = (s32[1,1048576]{1,0:T(1,128)}, s32[1,1048576]{1,0}) "
        "sort(s32[1,1048576]{1,0} %a, s32[1,1048576]{1,0} %iota.4), "
        "dimensions={1}, is_stable=true, to_apply=%region_2.7")
KERNEL = ("%hash_slide_tables.1 = (s32[1,8192,128]{2,1,0}, f32[1,8192,128]"
          "{2,1,0}) custom-call(s32[1,1,524288]{2,1,0} %b), "
          "custom_call_target=\"tpu_custom_call\"")
GATHER = ("%all-gather-start.2 = (f32[4096,4096]{1,0}, f32[4096,8192]{1,0}) "
          "all-gather-start(f32[4096,4096]{1,0} %p), channel_id=3")
FUSION = ("%fusion.1 = f32[1048576]{0:T(1024)} fusion(f32[1048576]{0} %c, "
          "s32[1048576]{0} %d), kind=kCustom, calls=%fused_computation.1")
COPY = ("%copy-start = (s32[1,1048576]{1,0}, s32[1,1048576]{1,0}, u32[]) "
        "copy-start(s32[1,1048576]{1,0} %e)")


@pytest.mark.parametrize("text,cls,name", [
    (SORT, "sort", "sort.0"), (KERNEL, "mosaic", "hash_slide_tables.1"),
    (GATHER, "collective", "all-gather-start.2"),
    (FUSION, "other", "fusion.1"), (COPY, "other", "copy-start")])
def test_ops_are_classified_from_their_hlo_text(text, cls, name):
    assert T.classify(text) == cls
    assert T.parse_op(text)[0] == name


def _trace():
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    ops = [T.Op(d0, SORT, "jit_engine", "sort", 10, 30),
           T.Op(d0, KERNEL, "jit_engine", "mosaic", 20, 50),  # overlaps
           T.Op(d0, FUSION, "jit_gen", "other", 70, 80),
           T.Op(d1, GATHER, "jit_engine", "collective", 0, 40),
           T.Op(d1, FUSION, "jit_engine", "other", 90, 130)]  # past window
    spans = [T.Span("engine_call", 0, 60), T.Span("block", 60, 100),
             T.Span("gen", 65, 85)]
    return T.Trace(ops, spans, [d0, d1])


def test_busy_is_the_union_of_op_intervals_in_the_window():
    t = _trace()
    assert t.window == (0, 100)
    # device 0: [10, 50) and [70, 80) -> 50; device 1: [0, 40), [90, 100)
    assert t.busy_s() == pytest.approx((50 + 50) / 2 / 1e9)
    assert t.busy_s(module="jit_engine") == pytest.approx((40 + 50) / 2 / 1e9)
    # op sums do not merge overlaps
    assert t.op_s(module="jit_engine", cls="mosaic") == pytest.approx(
        30 / 2 / 1e9)


def test_gaps_are_named_by_the_innermost_host_span():
    gaps = dict()
    for name, sec in _trace().gaps():
        gaps[name] = gaps.get(name, 0) + sec
    # device 0: [0,10) engine_call, [50,70) block/gen, [80,100) block;
    # device 1: [40,90) spans engine_call, block and gen
    assert set(gaps) <= {"engine_call", "block", "gen"}
    assert sum(gaps.values()) == pytest.approx((10 + 20 + 20 + 50) / 1e9)


def test_async_collectives_count_and_their_exposed_part():
    d0 = "/device:TPU:0"
    ops = [T.Op(d0, FUSION, "jit_summa", "other", 0, 40),
           T.Op(d0, GATHER, "jit_summa", "collective", 50, 60),  # sync
           # async all-gather from 20 to 70: under compute until 40, under
           # the sync gather from 50 to 60
           T.Op(d0, GATHER, "jit_summa", "collective", 20, 70, True),
           T.Op(d0, FUSION, "jit_summa", "other", 65, 90)]
    t = T.Trace(ops, [T.Span("engine_call", 0, 100)], [d0])
    # busy is the op stream alone: [0, 40), [50, 60), [65, 90)
    assert t.busy_s() == pytest.approx(75 / 1e9)
    assert t.count(cls="collective") == 1
    assert t.count(cls="collective", async_ops=True) == 2
    # collectives cover [20, 70); other ops cover [20, 40) and [65, 70)
    assert t.busy_s(cls="collective", async_ops=True) == pytest.approx(
        50 / 1e9)
    assert t.exposed_s("collective") == pytest.approx(25 / 1e9)


def test_unknown_device_kind_raises():
    with pytest.raises(T.UnknownDevice):
        T.load_peaks("TPU v99 imaginary")
    assert T.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.fixture(scope="module")
def recorded():
    return T.read_xspace(RECORDED)


def test_recorded_trace_splits_generator_from_engine(recorded):
    modules = {o.module for o in recorded.ops}
    assert {"jit_collection", "jit_spkadd_auto"} <= modules
    gen = recorded.busy_s(module="jit_collection")
    eng = recorded.busy_s(module="jit_spkadd_auto")
    assert 0 < gen < eng
    assert gen + eng == pytest.approx(recorded.busy_s(), rel=1e-9)


def test_recorded_trace_classes(recorded):
    eng = "jit_spkadd_auto"
    # er.hash: the hash_slide kernel, the compaction sort, no collective
    assert recorded.count(module=eng, cls="mosaic") >= 1
    assert recorded.count(module=eng, cls="sort") >= 1
    assert recorded.count(cls="collective", async_ops=True) == 0
    assert recorded.count(module="jit_collection", cls="sort") == 0
    kernel = recorded.op_s(module=eng, cls="mosaic")
    assert kernel > recorded.op_s(module=eng, cls="sort")
    assert recorded.devices == ["/device:TPU:0"]


def test_recorded_trace_busy_and_breakdown(recorded):
    busy, window = recorded.busy_s(), recorded.window_s()
    assert 0 < busy <= window
    assert busy <= recorded.op_s() + 1e-12
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "jit_spkadd_auto/hash_slide_tables.1"
    assert {g[0] for g in b["idle_gaps"]} <= {"gen", "engine_call", "block",
                                              "host"}
