"""Each driver end to end at toy traffic on the CPU, and the command's
refusal to run without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import run
from chipbench.tests.conftest import ROOT, TOY

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "check"]


def _assert_contract(out, workload):
    assert list(out) == CONTRACT_KEYS  # ``check`` last
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    want = {m["name"] for m in run.cell_spec(workload).end_to_end}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    for c in out["check"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("workload", ["er.vec", "er.hash", "rmat.hash"])
def test_spkadd_collection_cell_runs_correct(workload):
    with open(os.devnull, "w") as log:
        out = run.run(workload, 2**31 + 1, 0.2, False, traffic=TOY[workload],
                      require_accelerator=False, log=log)
    _assert_contract(out, workload)


def test_summa_cell_runs_correct(summa_results):
    _assert_contract(summa_results["sound"], "summa.2x2")
    assert summa_results["sound"]["device"]["count"] == 4


def _command(cwd, workload="er.vec"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_without_tpu_fails_and_prints_nothing():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_only_the_benchmark_without_the_program_fails(tmp_path):
    """A checkout holding only BENCHMARK.json and chipbench/ has no system
    to run: the run fails (here past the look for a chip) and prints no
    result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys, json; sys.path.insert(0, '.'); "
            "from chipbench import run; "
            "print(json.dumps(run.run('er.vec', 1, 0.1, False, "
            "traffic={'m': 64, 'n': 64, 'k': 2, 'nnz_per_matrix': 8}, "
            "require_accelerator=False)))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "No module named 'repro'" in proc.stderr
