"""Device time by program stage, read from the ops' HLO text."""
import os

import pytest

from chipbench import run, stages
from chipbench import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "vec_staged.xplane.pb")
UNSTAGED = os.path.join(DATA, "er_hash.xplane.pb")  # a program with no stages
STAGE_READERS = ("plan_ms.spkadd", "accumulate_ms.spkadd", "output_ms.spkadd",
                 "unstaged_pct.spkadd", "unstaged_pct.summa")
ENGINE_READERS = STAGE_READERS[:4]


def staged(text, path):
    return f'{text}, frontend_attributes={{obs_stage="{path}"}}'


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


def _win(steps=2, module="jit_engine"):
    return run.Window(setup_s=1.0, window_s=1.0, call_s=[0.5] * steps,
                      work=1.0, steps=steps, counts={},
                      modules={"gen": "jit_gen", "engine": module})


def _trace():
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    plan = staged(SORT, "spkadd.plan")
    acc = (KERNEL + ', frontend_attributes={kernel_metadata={},'
           'obs_stage="spkadd.accumulate"}')
    out = staged(FUSION, "summa.reduce/spkadd.output")
    ops = [T.Op(d0, plan, "jit_engine", T.classify(plan), 10, 30),
           T.Op(d0, acc, "jit_engine", T.classify(acc), 30, 70),
           T.Op(d0, FUSION, "jit_engine", "other", 70, 80),  # no stage
           T.Op(d0, staged(FUSION, "spkadd.plan"), "jit_gen", "other", 80, 90),
           T.Op(d1, out, "jit_engine", "other", 0, 20),
           T.Op(d1, staged(FUSION, "spkadd.plan"), "jit_engine", "other",
                90, 130),  # half past the window
           T.Op(d1, staged(GATHER, "summa.gather"), "jit_engine",
                "collective", 20, 40, async_op=True)]
    spans = [T.Span("engine_call", 0, 60), T.Span("block", 60, 110)]
    return T.Trace(ops, spans, [d0, d1])


@pytest.mark.parametrize("text", [SORT, KERNEL, GATHER, FUSION])
def test_the_attribute_leaves_class_and_name_alone(text):
    assert T.classify(staged(text, "spkadd.plan")) == T.classify(text)
    assert T.parse_op(staged(text, "summa.gather")) == T.parse_op(text)


def test_stage_of_reads_the_path():
    assert stages.stage_of(staged(FUSION, "summa.reduce/spkadd.plan")) \
        == "summa.reduce/spkadd.plan"
    assert stages.stage_of(KERNEL) is None


def test_stage_time_by_path_component_clipped_and_averaged():
    t = _trace()
    assert t.window == (0, 110)
    # plan: the sort (20) and the clipped fusion on chip 1 (90-110: 20);
    # the generator's staged op is another module
    assert stages.stage_s(t, "jit_engine", "spkadd.plan") == pytest.approx(
        (20 + 20) / 2 / 1e9)
    assert stages.stage_s(t, "jit_engine", "spkadd.accumulate") \
        == pytest.approx(40 / 2 / 1e9)
    # a component anywhere on the path counts; a prefix of a name does not
    assert stages.stage_s(t, "jit_engine", "spkadd.output") \
        == pytest.approx(20 / 2 / 1e9)
    assert stages.stage_s(t, "jit_engine", "summa.reduce") \
        == stages.stage_s(t, "jit_engine", "spkadd.output")
    assert stages.stage_s(t, "jit_engine", "spkadd") == 0
    # the asynchronous collective is left out, staged or not
    assert stages.stage_s(t, "jit_engine", "summa.gather") == 0
    assert stages.stage_s(t, "jit_engine", None) == pytest.approx(
        10 / 2 / 1e9)


def test_readers_per_step_and_unstaged_share():
    t, win = _trace(), _win(steps=2)
    read = {name: run.load_module("layers", name).read(t, win)
            for name in ENGINE_READERS}
    assert read["plan_ms.spkadd"] == pytest.approx(40 / 2 / 1e9 / 2 * 1e3)
    assert read["accumulate_ms.spkadd"] == pytest.approx(
        40 / 2 / 1e9 / 2 * 1e3)
    assert read["unstaged_pct.spkadd"] == pytest.approx(
        100 * 10 / (20 + 40 + 10 + 20 + 20))
    summa = run.load_module("layers", "unstaged_pct.summa").read(t, win)
    assert summa == pytest.approx(read["unstaged_pct.spkadd"])


def test_an_unstaged_program_reads_none():
    t = _trace()
    bare = T.Trace([o for o in t.ops if stages.stage_of(o.name) is None
                    or o.module != "jit_engine"], t.spans, t.devices)
    for name in STAGE_READERS:
        assert run.load_module("layers", name).read(bare, _win()) is None


def test_a_recorded_program_with_no_stages_reads_none():
    """The parent of the stages, as the benchmark traces it: every stage
    reader gives None, so its result line leaves the metric out."""
    t = T.read_xspace(UNSTAGED)
    win = _win(module="jit_spkadd_auto")
    assert t.count(module="jit_spkadd_auto") > 0
    for name in STAGE_READERS:
        assert run.load_module("layers", name).read(t, win) is None


@pytest.fixture(scope="module")
def recorded():
    return T.read_xspace(RECORDED)


def test_recorded_vec_trace_reads_every_engine_stage(recorded):
    module = "jit_spkadd_auto"
    steps = sum(1 for s in recorded.spans if s.name == "engine_call")
    win = _win(steps=steps, module=module)
    assert recorded.count(module=module, cls="mosaic") >= 1
    for name in ENGINE_READERS:
        value = run.load_module("layers", name).read(recorded, win)
        assert value is not None and value >= 0, name
    # the kernel is the accumulate stage
    kernel = recorded.op_s(module=module, cls="mosaic")
    assert stages.stage_s(recorded, module, "spkadd.accumulate") >= kernel
