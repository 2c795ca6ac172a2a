"""``chip_smoke.py``'s phases at tiny sizes on the CPU (interpret mode).

The script itself refuses a host without a TPU; these tests call its phase
functions directly, so every check a chip run makes — numpy reference,
bit-identity with the sorted regime, the stream service's admitted sums,
SUMMA and lossless compressed training — also guards each CPU test run.
"""
import importlib.util
import os

from repro.core import engine as E

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)


def test_engine_phase_every_regime_matches_numpy_and_sorted():
    """H at toy size: dispatch picks hash; every forced regime and the
    batched launch agree with numpy and, bit for bit, with sorted."""
    recs = smoke.phase_engine(
        "H", m=512, n=512, k=8, nnz=128, expect="hash",
        forced=tuple(r for r in E._CANONICAL if r != "sorted"), batch=2)
    assert [r["phase"] for r in recs][:2] == ["H/sorted", "H/auto"]
    assert recs[1]["regime"] == "hash" and recs[1]["parts"] == 1
    assert {r["regime"] for r in recs} == set(E._CANONICAL)
    assert recs[-1]["phase"] == "H/batched"


def test_duplicate_heavy_phase_every_regime_bit_identical():
    """D at toy size: nearly every key repeats 3+ times, so a regime that
    adds duplicates out of stream order breaks bit-identity with sorted."""
    recs = smoke.phase_engine(
        "D", m=16, n=8, k=16, nnz=64, expect="spa",
        forced=tuple(r for r in E._CANONICAL if r != "sorted"), batch=2)
    assert recs[0]["left_fold_exact"]
    assert {r["regime"] for r in recs} == set(E._CANONICAL)


def test_onehot_phase_takes_the_onehot_fold():
    recs = smoke.phase_onehot()
    assert recs[0]["fold"] == "onehot"


def test_stream_phase_matches_admitted_pushes():
    recs = smoke.phase_stream(tenants=4, duration=2.0, rate=2.0)
    assert recs[0]["admitted"] > 0 and recs[0]["flushes"] > 0


def test_engine_phase_rejects_a_wrong_dispatch():
    try:
        smoke.phase_engine("X", m=512, n=512, k=8, nnz=128, expect="vec")
    except smoke.SmokeFailure as e:
        assert "dispatched 'hash'" in str(e)
    else:
        raise AssertionError("a wrong dispatch must fail the phase")


def test_four_chip_phases_on_virtual_devices(multidevice):
    multidevice(f"""
import importlib.util, jax
spec = importlib.util.spec_from_file_location("chip_smoke", {os.path.join(ROOT, "chip_smoke.py")!r})
smoke = importlib.util.module_from_spec(spec); spec.loader.exec_module(smoke)
from repro.compat import make_mesh
from repro.configs import get_smoke_config
devs = jax.devices()[:4]
smoke.phase_summa(make_mesh((2, 2), ("data", "model"), devices=devs), n=64,
                  density=0.05)
smoke.phase_train(make_mesh((4,), ("data",), devices=devs),
                  cfg=get_smoke_config("smollm-135m"), seq=16, batch=4)
print("four-chip phases ok")
""", n_devices=4)
