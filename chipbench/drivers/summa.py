"""Driver: one distributed SpGEMM (2-D SUMMA) of fresh operands per step.

Each step makes A and B (``n x n``, ER placement) on the device from
``(seed, step)``, sharded ``(data, model)`` over the configuration's mesh,
and multiplies them with ``repro.core.spgemm.spgemm_summa``, jitted, at
the configuration's matmul precision. The check regenerates a sampled
step's operands and compares C with ``reference.matmul_reference``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import reference
from chipbench.drivers.common import seed_key, step_arg


class Cell:
    def __init__(self, *, config: dict, traffic: dict, seed: int, devices,
                 gen, system=None):
        from repro.compat import make_mesh
        from repro.core import spgemm

        shape = tuple(config["mesh"])
        axes = tuple(config["mesh_axes"])
        mesh = make_mesh(shape, axes, devices=devices[:math.prod(shape)])
        sharding = NamedSharding(mesh, P(*axes))
        n, density = int(traffic["n"]), float(traffic["density"])
        self.precision = config["matmul_precision"]
        self.work_per_call = 1
        self.base = seed_key(seed)
        algorithm = config["algorithm"]

        def operands(base, step):
            ka, kb = jax.random.split(jax.random.fold_in(base, step))
            return (gen.dense(ka, n=n, density=density),
                    gen.dense(kb, n=n, density=density))

        fn = system if system is not None else spgemm.spgemm_summa

        def spgemm_summa(a, b):
            return fn(a, b, mesh=mesh, algorithm=algorithm)

        self._operands = jax.jit(operands, out_shardings=(sharding, sharding))
        self._call = jax.jit(spgemm_summa)
        self.modules = {"gen": "jit_operands", "engine": "jit_spgemm_summa"}

    def inputs(self, step: int):
        return self._operands(self.base, step_arg(step))

    def call(self, ab):
        with jax.default_matmul_precision(self.precision):
            return self._call(*ab)

    def warm(self) -> None:
        jax.block_until_ready(self.call(self.inputs(2**32 - 1)))

    def counts(self, out) -> dict:
        return {}

    def fetch(self, out):
        return np.asarray(out)

    def check(self, step: int, got) -> dict:
        a, b = (np.asarray(x) for x in self.inputs(step))
        return reference.compare_matmul(got, reference.matmul_reference(a, b))


def control(config: dict):
    """The reference in the program's place at the precision below the
    configuration's ``highest``: ``high``, three bfloat16 passes (each
    operand split into a bfloat16 head and tail, the tail-by-tail product
    left out), written out so that it means the same on every backend.

    The head is rounded with integer arithmetic: a float32 -> bfloat16 ->
    float32 round trip may be removed by XLA (it allows excess precision),
    which leaves a zero tail and one bfloat16 pass."""
    if config["matmul_precision"] != "highest":
        raise ValueError("the control is written for float32 at highest")

    def split(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        head = jax.lax.bitcast_convert_type(
            (bits + jnp.uint32(0x8000)) & jnp.uint32(0xFFFF0000), jnp.float32)
        return head.astype(jnp.bfloat16), (x - head).astype(jnp.bfloat16)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    def matmul_high(a, b, mesh=None, algorithm=None):
        del mesh, algorithm
        (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
        return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))

    return matmul_high
