"""Driver: one SpKAdd of a fresh collection per step.

Each step makes k matrices from ``(seed, step)`` on the device with the
configuration's generator and adds them with ``repro.core.engine.
spkadd_auto``, jitted, as a user calls it. The check regenerates a sampled
step's collection and compares the whole padded result (keys, ``nnz``,
values) with ``reference.sum_reference``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference
from chipbench.drivers.common import seed_key, step_arg


class Cell:
    def __init__(self, *, config: dict, traffic: dict, seed: int, devices,
                 gen, system=None):
        from repro.core import engine
        from repro.core.sparse import PaddedCOO

        m, n = int(traffic["m"]), int(traffic["n"])
        k, nnz = int(traffic["k"]), int(traffic["nnz_per_matrix"])
        if m * n > config["max_mn"]:
            raise ValueError(f"m*n = {m * n} exceeds the configuration's "
                             f"max_mn {config['max_mn']}")
        self.shape = (m, n)
        self.work_per_call = k * nnz
        self.base = jax.device_put(seed_key(seed), devices[0])
        params = config.get("params", {})

        def collection(base, step):
            keys, vals = gen.triples(jax.random.fold_in(base, step), m=m,
                                     n=n, k=k, nnz=nnz, params=params)
            count = jnp.asarray(nnz, jnp.int32)
            return [PaddedCOO(keys[i], vals[i], count, (m, n))
                    for i in range(k)]

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
        return (np.asarray(out.keys), np.asarray(out.vals), int(out.nnz))

    def check(self, step: int, got) -> dict:
        mats = self.inputs(step)
        keys = np.stack([np.asarray(a.keys) for a in mats])
        vals = np.stack([np.asarray(a.vals) for a in mats])
        m, n = self.shape
        return reference.compare_sum(*got, reference.sum_reference(keys, vals),
                                     sentinel=m * n)


def control(config: dict):
    """The reference in the program's place, its values summed in bfloat16
    (the precision below the configuration's float32): a stable sort of
    the concatenated keys and a segment sum, in plain ``jax.numpy``."""
    del config

    def spkadd_bf16(mats):
        m, n = mats[0].shape
        keys = jnp.concatenate([a.keys for a in mats])
        vals = jnp.concatenate([a.vals for a in mats]).astype(jnp.bfloat16)
        order = jnp.argsort(keys, stable=True)
        ks, vs = keys[order], vals[order]
        first = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
        gid = jnp.cumsum(first) - 1
        cap = keys.shape[0]
        sums = jax.ops.segment_sum(vs, gid, num_segments=cap)
        nnz = first.sum().astype(jnp.int32)
        out_keys = jnp.full((cap,), m * n, jnp.int32).at[
            jnp.where(first, gid, cap)].set(ks, mode="drop")
        live = jnp.arange(cap) < nnz
        out_vals = jnp.where(live, sums, 0).astype(jnp.float32)
        return _Result(out_keys, out_vals, nnz)

    return spkadd_bf16


class _Result(NamedTuple):
    """The fields of a padded sparse result that the check reads."""
    keys: jax.Array
    vals: jax.Array
    nnz: jax.Array
