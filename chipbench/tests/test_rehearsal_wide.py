"""The two cells that run the engine's ``sorted`` regime, end to end at toy
traffic on the CPU: ``rmat_wide.s22`` at scale 16 (m*n = 2**32, two-word
keys) and ``er.k128`` at k = 128; and the wide driver's bfloat16 control,
which the cell's ``val_err`` limit has to refuse."""
import os

import jax
import numpy as np
import pytest

from chipbench import run
from chipbench.tests.test_rehearsal import _assert_contract

#: toy traffic per cell; both keep dispatch on ``sorted``
TOY = {
    "rmat_wide.s22": {"m": 65536, "n": 65536, "k": 8,
                      "nnz_per_matrix": 131072},
    "er.k128": {"m": 256, "n": 256, "k": 128, "nnz_per_matrix": 3},
}


def _run(workload, system=None):
    with open(os.devnull, "w") as log:
        return run.run(workload, 2**31 + 5, 0.2, False, system=system,
                       traffic=TOY[workload], require_accelerator=False,
                       log=log)


@pytest.mark.parametrize("workload", sorted(TOY))
def test_sorted_regime_cell_runs_correct(workload):
    from repro.core import engine as E

    toy = TOY[workload]
    m, n, k = toy["m"], toy["n"], toy["k"]
    cap = k * toy["nnz_per_matrix"]
    sig = E.RegimeSignals(k=k, density=cap / (m * n),
                          compression=E.estimate_compression(cap, m * n),
                          accum_elems=m * n)
    assert E.select_algorithm(sig) == "sorted"
    _assert_contract(_run(workload), workload)


def test_wide_control_fails_val_err():
    spec = run.cell_spec("rmat_wide.s22")
    control = run.load_module("drivers", spec.config["driver"]).control(
        spec.config)
    out = _run("rmat_wide.s22", system=control)
    assert out["correct"] is False
    assert out["check"]["key_mismatch"]["value"] == 0
    assert out["check"]["val_err"]["value"] > out["check"]["val_err"]["limit"]


def test_wide_generator_draws_graph500_levels():
    """Two int32 words per key, in range. Each level sets a row bit with
    probability c + d and a column bit with b + d, so the heaviest vertex
    (no bit set before the scramble, a bijection) has a + b = 0.76 of the
    edges at each of the 16 levels: 0.76**16 of them as a row, and
    a + c = 0.76 likewise as a column."""
    gen = run.load_module("gen", "rmat_wide")
    params = run.cell_spec("rmat_wide.s22").config["params"]
    scale, k, nnz = 16, 8, 131072
    (cols, rows), vals = gen.triples(jax.random.key(3), m=1 << scale,
                                     n=1 << scale, k=k, nnz=nnz,
                                     params=params)
    assert cols.shape == rows.shape == vals.shape == (k, nnz)
    assert cols.dtype == rows.dtype == np.int32
    want = k * nnz * (params["a"] + params["b"]) ** scale  # about 12995
    for words in (cols, rows):
        w = np.asarray(words).reshape(-1)
        assert 0 <= w.min() and w.max() < 1 << scale
        assert abs(np.bincount(w).max() - want) < 0.05 * want
