"""The comparison catches what it is there to catch.

Each test drives a whole run (generator, window, sampled check) at toy
traffic on the CPU, skipping only the look for a chip, with the timed path
broken underneath or with the control, the reference at the precision
below the configuration's, in the program's place. ``correct`` must come
out false every time, and true for the unbroken program.
"""
import os

import pytest

from chipbench import run
from chipbench.tests.conftest import TOY


def _run(workload, system=None, seed=2**31 + 3):
    with open(os.devnull, "w") as log:
        return run.run(workload, seed, 0.2, False, system=system,
                       traffic=TOY[workload], require_accelerator=False,
                       log=log)


def _engine_faults():
    from repro.core import engine
    from repro.core.sparse import concat, with_capacity

    auto = engine.spkadd_auto

    def state_unchanged(mats):  # the accumulator left at its first matrix
        return with_capacity(mats[0], concat(mats).cap)

    def half_batch(mats):  # half the matrices, the sum scaled up from them
        out = auto(mats[: len(mats) // 2])
        return with_capacity(out._replace(vals=out.vals * 2),
                             concat(mats).cap)

    def answer_altered(mats):
        out = auto(mats)
        return out._replace(vals=out.vals.at[0].add(1.0))

    return {"state_unchanged": state_unchanged, "half_batch": half_batch,
            "answer_altered": answer_altered}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("workload", ["er.vec", "er.hash", "rmat.hash"])
def test_engine_cell_fault_is_caught(workload, fault, monkeypatch):
    from repro.core import engine
    broken = _engine_faults()[fault]
    broken.__name__ = "spkadd_auto"
    monkeypatch.setattr(engine, "spkadd_auto", broken)
    out = _run(workload)
    assert not out["correct"]
    assert out["failed"] > 0


@pytest.mark.parametrize("workload", ["er.vec", "er.hash", "rmat.hash"])
def test_engine_cell_control_is_caught(workload):
    from chipbench.drivers import spkadd_collection
    config = run.cell_spec(workload).config
    out = _run(workload, system=spkadd_collection.control(config))
    assert not out["correct"]
    assert out["check"]["key_mismatch"]["value"] == 0
    assert out["check"]["val_err"]["value"] > config["limits"]["val_err"]


@pytest.mark.parametrize("fault", ["control", "state_unchanged",
                                   "half_batch", "answer_altered",
                                   "exchange_left_out"])
def test_summa_fault_is_caught(summa_results, fault):
    assert not summa_results[fault]["correct"], summa_results[fault]
