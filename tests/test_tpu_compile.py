"""Compile the engine's Pallas kernels for a described TPU v5e through Mosaic.

Interpret mode accepts kernels the TPU compiler refuses (unaligned blocks,
scalar VMEM stores, primitives Mosaic cannot lower, VMEM overruns), so
each kernel the engine launches is compiled here with ``interpret=False``
at the geometry ``chip_smoke.py`` runs it at: the sliding-hash kernel at H
(one 2^20-slot table), and the partitioned kernel's serial fold at V and
its one-hot fold at O (the one-hot fold's small tile), each alone and as a
batch of four. Nothing
runs; the topology is described, not attached.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine as E
from repro.kernels import VMEM_BUDGET_BYTES
from repro.kernels import ops as kops
from repro.kernels.hash_slide import hash_slide_raw
from repro.kernels.partition import partitioned_accumulate_raw


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.compat import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_hash_slide_compiles_at_h(one_chip):
    cap, m, n = 32 * 16384, 16384, 16384
    g = kops.hash_launch_geometry(cap, m=m, n=n)
    assert (g.table_size, g.parts) == (1 << 20, 1)
    stream = lambda dt: jax.ShapeDtypeStruct((1, g.num_chunks * g.chunk), dt,
                                             sharding=one_chip)
    _compile(lambda k, v: hash_slide_raw(
        k, v, mn=m * n, table_size=g.table_size, part_span=g.part_span,
        parts=g.parts, chunk=g.chunk, interpret=False),
        stream(jnp.int32), stream(jnp.float32))


@pytest.mark.parametrize("fold,batch,m,k,nnz", [
    ("serial", 1, 8192, 64, 65536),
    ("serial", 4, 8192, 64, 65536),
    ("onehot", 1, 64, 8, 256),
    ("onehot", 4, 64, 8, 256),
])
def test_partitioned_fold_compiles(one_chip, fold, batch, m, k, nnz):
    g = kops.partitioned_launch_geometry(k * nnz, m=m, n=m)
    assert E._partition_fold("vec", g, VMEM_BUDGET_BYTES, None) == fold
    shape = lambda cols, dt: jax.ShapeDtypeStruct((batch, cols), dt,
                                                  sharding=one_chip)
    cap_pad = g.num_chunks * g.chunk
    _compile(lambda kk, vv, c, p: partitioned_accumulate_raw(
        kk, vv, c, p, mn=m * m, part_elems=g.part_elems, parts=g.parts,
        chunk=g.chunk, fold=fold, interpret=False),
        shape(cap_pad, jnp.int32), shape(cap_pad, jnp.float32),
        shape(g.max_steps, jnp.int32), shape(g.max_steps, jnp.int32))
