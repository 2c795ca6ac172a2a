"""Plain references and the comparisons that decide ``correct``.

Nothing here imports the program under ``src/``: a reference takes the
inputs the benchmark generated and computes the same sum or product in
float64 with numpy and scipy. Each comparison returns the numbers that a
cell's ``limits`` (in its configuration file) bound from above.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def sum_reference(keys: np.ndarray, vals: np.ndarray):
    """SpKAdd of COO triples: the distinct keys (ascending), the float64 sum
    of each key's values, and the sum of their magnitudes (the scale that
    rounding errors of the sum are measured against)."""
    uniq, inv = np.unique(keys.reshape(-1), return_inverse=True)
    v = vals.reshape(-1).astype(np.float64)
    sums = np.bincount(inv, weights=v, minlength=len(uniq))
    mags = np.bincount(inv, weights=np.abs(v), minlength=len(uniq))
    return uniq, sums, mags


def compare_sum(out_keys: np.ndarray, out_vals: np.ndarray, out_nnz: int,
                ref, sentinel: int) -> dict:
    """Compare a padded sparse result with :func:`sum_reference`.

    ``key_mismatch`` counts the structural faults: each slot among the first
    ``len(uniq)`` whose key differs, the gap between ``nnz`` and the
    reference's count, and each slot past it that is not padding (the
    sentinel key with a value of exactly 0). ``val_err`` is the largest
    ``|got - want| / sum |terms|`` over the distinct keys.
    """
    uniq, sums, mags = ref
    d = len(uniq)
    if len(out_keys) < d:
        return {"key_mismatch": d - len(out_keys) + abs(out_nnz - d),
                "val_err": float("inf")}
    head = out_keys[:d]
    tail_keys, tail_vals = out_keys[d:], out_vals[d:]
    mismatch = (int(np.count_nonzero(head != uniq)) + abs(int(out_nnz) - d)
                + int(np.count_nonzero((tail_keys != sentinel)
                                       | (tail_vals != 0))))
    err = np.abs(out_vals[:d].astype(np.float64) - sums) / mags
    return {"key_mismatch": mismatch,
            "val_err": float(err.max()) if d else 0.0}


def matmul_reference(a: np.ndarray, b: np.ndarray):
    """``A @ B`` in float64, and ``|A| @ |B|``, the scale of each entry's
    rounding error (both as dense arrays)."""
    a64 = sp.csr_matrix(a, dtype=np.float64)
    b64 = sp.csr_matrix(b, dtype=np.float64)
    return (a64 @ b64).toarray(), (abs(a64) @ abs(b64)).toarray()


def compare_matmul(c: np.ndarray, ref) -> dict:
    """``c_err`` is the largest ``|got - want| / (|A| @ |B|)`` over the
    entries where some product is nonzero; ``c_stray`` counts the entries
    where none is and ``c`` is not exactly 0."""
    want, mag = ref
    live = mag > 0
    err = np.abs(c[live].astype(np.float64) - want[live]) / mag[live]
    return {"c_err": float(err.max()) if err.size else 0.0,
            "c_stray": int(np.count_nonzero(c[~live]))}
