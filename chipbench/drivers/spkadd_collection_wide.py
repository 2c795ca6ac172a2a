"""Driver: one SpKAdd of a fresh collection per step, keys past int32.

As ``spkadd_collection``, for a shape whose ``m * n`` is 2**31 or more:
the generator gives each key as its two int32 words ``(cols, rows)``, the
k matrices hold them as ``repro.core.sparse.WideKeys``, and
``repro.core.engine.spkadd_auto``, jitted, adds them as a user calls it.
The check joins the words into int64 keys ``col * m + row`` on the host
(the sentinel ``(n, 0)`` becomes ``m * n``) and compares the whole padded
result with ``reference.sum_reference``, as the narrow driver does.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference
from chipbench.drivers.common import seed_key, step_arg


def join(col, row, m: int) -> np.ndarray:
    """Two int32 key words as int64 CSC keys ``col * m + row``."""
    return np.asarray(col).astype(np.int64) * m + np.asarray(row)


class Cell:
    def __init__(self, *, config: dict, traffic: dict, seed: int, devices,
                 gen, system=None):
        from repro.core import engine
        from repro.core.sparse import PaddedCOO, WideKeys

        m, n = int(traffic["m"]), int(traffic["n"])
        k, nnz = int(traffic["k"]), int(traffic["nnz_per_matrix"])
        self.shape = (m, n)
        self.work_per_call = k * nnz
        self.base = jax.device_put(seed_key(seed), devices[0])
        params = config.get("params", {})

        def collection(base, step):
            (cols, rows), vals = gen.triples(
                jax.random.fold_in(base, step), m=m, n=n, k=k, nnz=nnz,
                params=params)
            count = jnp.asarray(nnz, jnp.int32)
            return [PaddedCOO(WideKeys(cols[i], rows[i]), vals[i], count,
                              (m, n)) for i in range(k)]

        fn = system if system is not None else engine.spkadd_auto
        self._collection = jax.jit(collection)
        self._call = jax.jit(fn)
        self.modules = {"gen": "jit_collection",
                        "engine": f"jit_{fn.__name__}"}

    def inputs(self, step: int):
        return self._collection(self.base, step_arg(step))

    def call(self, mats):
        return self._call(mats)

    def warm(self) -> None:
        """One call of each program, on a step the window never reaches."""
        jax.block_until_ready(self.call(self.inputs(2**32 - 1)))

    def counts(self, out) -> dict:
        return {"in_nnz": self.work_per_call, "out_nnz": out.nnz}

    def fetch(self, out):
        return (join(out.keys.col, out.keys.row, self.shape[0]),
                np.asarray(out.vals), int(out.nnz))

    def check(self, step: int, got) -> dict:
        mats = self.inputs(step)
        m, n = self.shape
        keys = np.stack([join(a.keys.col, a.keys.row, m) for a in mats])
        vals = np.stack([np.asarray(a.vals) for a in mats])
        return reference.compare_sum(*got, reference.sum_reference(keys, vals),
                                     sentinel=m * n)


def control(config: dict):
    """The reference in the program's place, its values summed in bfloat16
    (the precision below the configuration's float32): one stable sort of
    the concatenated key words and a segment sum, in plain ``jax.numpy``."""
    del config

    def spkadd_bf16(mats):
        m, n = mats[0].shape
        cols = jnp.concatenate([a.keys.col for a in mats])
        rows = jnp.concatenate([a.keys.row for a in mats])
        vals = jnp.concatenate([a.vals for a in mats]).astype(jnp.bfloat16)
        cs, rs, vs = jax.lax.sort((cols, rows, vals), num_keys=2,
                                  is_stable=True)
        first = jnp.concatenate([jnp.ones((1,), bool),
                                 (cs[1:] != cs[:-1]) | (rs[1:] != rs[:-1])])
        gid = jnp.cumsum(first) - 1
        cap = cols.shape[0]
        sums = jax.ops.segment_sum(vs, gid, num_segments=cap)
        nnz = first.sum().astype(jnp.int32)
        slot = jnp.where(first, gid, cap)
        out_cols = jnp.full((cap,), n, jnp.int32).at[slot].set(cs,
                                                               mode="drop")
        out_rows = jnp.zeros((cap,), jnp.int32).at[slot].set(rs, mode="drop")
        live = jnp.arange(cap) < nnz
        out_vals = jnp.where(live, sums, 0).astype(jnp.float32)
        return _Result(_Words(out_cols, out_rows), out_vals, nnz)

    return spkadd_bf16


class _Words(NamedTuple):
    col: jax.Array
    row: jax.Array


class _Result(NamedTuple):
    """The fields of a padded sparse result that the check reads."""
    keys: _Words
    vals: jax.Array
    nnz: jax.Array
