"""Distributed behaviour (subprocess with fake CPU devices): sparse allreduce
schedules, compressed training equivalence, distributed SpGEMM."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_sparse_allreduce_schedules_agree(multidevice):
    multidevice(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.compat import shard_map
from repro.core.topk import topk_global
from repro.core import allreduce as AR

from repro.compat import make_mesh
mesh = make_mesh((8,), ('data',))
rng = np.random.default_rng(2)
size, kk = 1000, 50
G = rng.standard_normal((8, size)).astype(np.float32)

def worker(g):
    u = topk_global(g.reshape(-1), kk)
    return {s: AR.sparse_allreduce(u, 'data', s)
            for s in ['gather_kway', 'tree_2way', 'ring_2way']}

f = shard_map(worker, mesh=mesh, in_specs=(P('data'),), out_specs=P('data'))
res = f(jnp.asarray(G))
expect = np.zeros(size, np.float32)
for i in range(8):
    idx = np.argsort(-np.abs(G[i]))[:kk]
    s = np.zeros(size, np.float32); s[idx] = G[i][idx]; expect += s
expect /= 8
for sched, v in res.items():
    v = np.asarray(v).reshape(8, size)
    for i in range(8):
        np.testing.assert_allclose(v[i], expect, rtol=1e-5, atol=1e-6,
                                   err_msg=sched)
print('schedules ok')
""")


def test_gather_kway_vec_accumulator_bit_identical(multidevice):
    """The gather_kway schedule routed through the lane-parallel vec
    accumulator (kernels/vec_accum) must return the *same bits* as the XLA
    scatter — both fold per-key contributions in stream order."""
    multidevice(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.compat import shard_map
from repro.core.topk import topk_global
from repro.core import allreduce as AR

from repro.compat import make_mesh
mesh = make_mesh((8,), ('data',))
rng = np.random.default_rng(5)
size, kk = 400, 40
G = rng.standard_normal((8, size)).astype(np.float32)

def worker(g):
    u = topk_global(g.reshape(-1), kk)
    return (AR.sparse_allreduce(u, 'data', 'gather_kway'),
            AR.sparse_allreduce(u, 'data', 'gather_kway', accumulator='vec'))

# check_vma=False: no replication rule exists for pallas_call
f = shard_map(worker, mesh=mesh, in_specs=(P('data'),), out_specs=P('data'),
              check_vma=False)
scatter, vec = f(jnp.asarray(G))
np.testing.assert_array_equal(np.asarray(scatter), np.asarray(vec))
print('vec accumulator bitwise ok')
""")


def test_compressed_training_matches_dense_at_full_k(multidevice):
    """k_fraction=1.0 (lossless sparse allreduce) must track dense DP
    training step-for-step."""
    multidevice(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.common import ModelConfig, ShapeConfig
from repro.models import build_model
from repro.train import (make_train_step, make_compressed_train_step,
                         init_ef_state, TrainHParams)
from repro.optim import adamw_init
from repro.data import make_batch

cfg = ModelConfig(arch_id='t', family='dense', n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                  compute_dtype='float32')
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
opt = adamw_init(params)
hp = TrainHParams(ce_chunk=16, attn_chunk=16, remat=False, total_steps=100,
                  warmup=0)
shape = ShapeConfig('t', 'train', 32, 8)
from repro.compat import make_mesh
mesh = make_mesh((8,), ('data',))

dense = jax.jit(make_train_step(m, hp))
comp = jax.jit(make_compressed_train_step(m, mesh, hp, k_fraction=1.0,
                                          selector='global'))
ef = init_ef_state(params, 8)
pd, od = params, opt
pc, oc = params, opt
for s in range(3):
    batch = make_batch(cfg, shape, s)
    bsh = jax.tree.map(lambda x: jax.device_put(
        x, NamedSharding(mesh, P(*(('data',) + (None,)*(x.ndim-1))))), batch)
    pd, od, md = dense(pd, od, bsh)
    pc, oc, ef, mc = comp(pc, oc, ef, bsh)
    assert abs(float(md['loss']) - float(mc['loss'])) < 1e-4, (s, md, mc)
for a, b in zip(jax.tree.leaves(pd), jax.tree.leaves(pc)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-5)
print('lossless compressed == dense ok')
""")


def test_compressed_2d_matches_dense_at_full_k(multidevice):
    """On a (4, 2) ('data','model') mesh, the DP×TP composition with
    k_fraction=1.0 (lossless per-shard top-k) must track the dense-allreduce
    step loss- and parameter-for-parameter; the per-shard EF residuals must
    stay exactly representable-zero-ish."""
    multidevice(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.common import ModelConfig, ShapeConfig
from repro.models import build_model
from repro.train import (make_train_step, make_compressed_train_step,
                         init_ef_state, TrainHParams)
from repro.sharding.params import ef_shardings
from repro.optim import adamw_init
from repro.data import make_batch

cfg = ModelConfig(arch_id='t', family='dense', n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                  compute_dtype='float32')
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
opt = adamw_init(params)
hp = TrainHParams(ce_chunk=16, attn_chunk=16, remat=False, total_steps=100,
                  warmup=0)
shape = ShapeConfig('t', 'train', 32, 8)
from repro.compat import make_mesh
mesh = make_mesh((4, 2), ('data', 'model'))

dense = jax.jit(make_train_step(m, hp))
# min_compress_elems lowered so the tiny model's matrices take the sparse
# path instead of the dense-psum small-leaf fallback
comp = jax.jit(make_compressed_train_step(m, mesh, hp, k_fraction=1.0,
                                          selector='global',
                                          min_compress_elems=1024))
ef = init_ef_state(params, 4, model_shards=2)
ef = jax.tree.map(jax.device_put, ef, ef_shardings(ef, mesh))
pd, od = params, opt
pc, oc = params, opt
for s in range(3):
    batch = make_batch(cfg, shape, s)
    bsh = jax.tree.map(lambda x: jax.device_put(
        x, NamedSharding(mesh,
                         P(*((('data', 'model'),) + (None,)*(x.ndim-1))))),
        batch)
    pd, od, md = dense(pd, od, bsh)
    pc, oc, ef, mc = comp(pc, oc, ef, bsh)
    assert abs(float(md['loss']) - float(mc['loss'])) < 1e-4, (s, md, mc)
for a, b in zip(jax.tree.leaves(pd), jax.tree.leaves(pc)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-5)
for r in jax.tree.leaves(ef):
    assert float(jnp.abs(r).max()) < 1e-6  # lossless => no residual
print('2d lossless compressed == dense ok')
""")


def test_compressed_2d_all_schedules_and_model_reduce(multidevice):
    """k_fraction<1 on the (4, 2) mesh: every SpKAdd schedule × both
    model-axis combines must produce the SAME update (identical selected
    values, different reduction order ⇒ allclose), and EF training must
    make progress."""
    multidevice(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.common import ModelConfig, ShapeConfig
from repro.models import build_model
from repro.train import make_compressed_train_step, init_ef_state, TrainHParams
from repro.sharding.params import ef_shardings
from repro.optim import adamw_init
from repro.data import make_batch

cfg = ModelConfig(arch_id='t', family='dense', n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                  compute_dtype='float32')
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
opt = adamw_init(params)
hp = TrainHParams(ce_chunk=16, attn_chunk=16, remat=False, peak_lr=3e-3,
                  total_steps=1000, warmup=0, weight_decay=0.0)
from repro.compat import make_mesh
mesh = make_mesh((4, 2), ('data', 'model'))
shape = ShapeConfig('t', 'train', 32, 8)
batch = make_batch(cfg, shape, 0)
bsh = jax.tree.map(lambda x: jax.device_put(
    x, NamedSharding(mesh, P(*((('data', 'model'),) + (None,)*(x.ndim-1))))),
    batch)

outs = {}
for sched in ('gather_kway', 'tree_2way', 'ring_2way'):
    for mr in ('reduce_scatter', 'psum'):
        step = jax.jit(make_compressed_train_step(
            m, mesh, hp, k_fraction=0.1, selector='global', schedule=sched,
            model_reduce=mr, min_compress_elems=1024))
        ef = init_ef_state(params, 4, model_shards=2)
        ef = jax.tree.map(jax.device_put, ef, ef_shardings(ef, mesh))
        p, o, ef, met = step(params, opt, ef, bsh)
        assert np.isfinite(float(met['loss'])), (sched, mr)
        # compression actually happened: some residual is nonzero
        assert max(float(jnp.abs(r).max()) for r in jax.tree.leaves(ef)) > 0
        outs[(sched, mr)] = (float(met['loss']), p)
ref_loss, ref_p = outs[('gather_kway', 'reduce_scatter')]
for key, (loss, p) in outs.items():
    assert abs(loss - ref_loss) < 1e-5, (key, loss, ref_loss)
    for a, b in zip(jax.tree.leaves(ref_p), jax.tree.leaves(p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5, err_msg=str(key))

# EF makes progress over steps at 10% density
step = jax.jit(make_compressed_train_step(
    m, mesh, hp, k_fraction=0.1, schedule='gather_kway',
    min_compress_elems=1024))
ef = init_ef_state(params, 4, model_shards=2)
ef = jax.tree.map(jax.device_put, ef, ef_shardings(ef, mesh))
p, o = params, opt
losses = []
for s in range(6):
    p, o, ef, met = step(p, o, ef, bsh)
    losses.append(float(met['loss']))
assert losses[-1] < losses[0], losses
print('2d schedules agree; EF converges:', losses[0], '->', losses[-1])
""")


def test_spgemm_summa_all_algorithms(multidevice):
    multidevice(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.spgemm import spgemm_summa
rng = np.random.default_rng(3)
from repro.compat import make_mesh
mesh = make_mesh((2, 2), ('data', 'model'))
M, K, N = 32, 24, 16
def sprand(m, n, frac=0.2):
    d = np.zeros((m, n), np.float32)
    nz = int(m*n*frac)
    idx = rng.choice(m*n, nz, replace=False)
    d.flat[idx] = rng.standard_normal(nz)
    return d
A, B = sprand(M, K), sprand(K, N)
for alg in ['incremental', 'tree', 'sorted', 'spa']:
    C = spgemm_summa(jnp.asarray(A), jnp.asarray(B), mesh, algorithm=alg)
    np.testing.assert_allclose(np.asarray(C), A@B, rtol=1e-4, atol=1e-5,
                               err_msg=alg)
print('spgemm ok')
""", n_devices=4)


def test_error_feedback_converges(multidevice):
    """Aggressive compression (1%) with EF still reduces loss over steps."""
    multidevice(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.common import ModelConfig, ShapeConfig
from repro.models import build_model
from repro.train import make_compressed_train_step, init_ef_state, TrainHParams
from repro.optim import adamw_init
from repro.data import make_batch

cfg = ModelConfig(arch_id='t', family='dense', n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=256, vocab=128,
                  compute_dtype='float32')
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
opt = adamw_init(params)
hp = TrainHParams(ce_chunk=16, attn_chunk=16, remat=False, peak_lr=3e-3,
                  total_steps=1000, warmup=0, weight_decay=0.0)
from repro.compat import make_mesh
mesh = make_mesh((4,), ('data',))
step = jax.jit(make_compressed_train_step(m, mesh, hp, k_fraction=0.01))
ef = init_ef_state(params, 4)
shape = ShapeConfig('t', 'train', 32, 4)
batch = make_batch(cfg, shape, 0)
bsh = jax.tree.map(lambda x: jax.device_put(
    x, NamedSharding(mesh, P(*(('data',) + (None,)*(x.ndim-1))))), batch)
losses = []
for s in range(8):
    params, opt, ef, metrics = step(params, opt, ef, bsh)
    losses.append(float(metrics['loss']))
assert losses[-1] < losses[0], losses
print('EF converges:', losses[0], '->', losses[-1])
""", n_devices=4)


@pytest.mark.parametrize("mesh", [(2, 1), (2, 2)], ids=["dp", "dp_tp"])
def test_sparse_allreduce_bench_snippet_runs(multidevice, mesh):
    """The byte benchmark's child program, at toy size, on a data-parallel
    mesh and on a data x model mesh: both branches build their mesh and
    emit the dense and compressed records."""
    sys.path.insert(0, REPO)  # for `benchmarks` (namespace package)
    from benchmarks.sparse_allreduce_bytes import SNIPPET
    knobs = dict(layers=1, d_model=64, d_ff=128, vocab=256, batch=8, seq=8,
                 fracs=(0.5,), scheds=("gather_kway",),
                 min_compress_elems=1024, mesh=list(mesh))
    out = multidevice(f"import sys; sys.argv = ['snippet', "
                      f"{json.dumps(knobs)!r}]\n" + SNIPPET,
                      n_devices=mesh[0] * mesh[1])
    tag = "allreduce" if mesh[1] == 1 else "allreduce_2x2"
    assert f"{tag}/dense/coll_bytes," in out
    assert f"{tag}/topk0.5/gather_kway/step," in out
