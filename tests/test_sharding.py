"""Sharding-rule unit tests: param/batch/cache PartitionSpecs (pure logic,
validated on a 512-device mesh in a subprocess)."""


def test_param_specs_fsdp_tp(multidevice):
    multidevice(r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_production_mesh
from repro.sharding.params import param_spec, batch_spec, cache_spec
from repro.configs import get_config

mesh = make_production_mesh()
DK = jax.tree_util.DictKey

def spec_of(name, shape):
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32)
    return param_spec((DK(name),), leaf, mesh)

# 2D weights: fsdp x tp
assert spec_of('wq', (4096, 4096)) == P('data', 'model')
assert spec_of('wo', (4096, 4096)) == P('model', 'data')
assert spec_of('embed', (262144, 5376)) == P('model', 'data')
# stacked layer dims pad with None
assert spec_of('w1', (48, 4096, 16384)) == P(None, 'data', 'model')
# non-divisible axes are dropped, not errors
assert spec_of('wq', (4095, 4096)) == P(None, 'model')
# norms replicated
assert spec_of('ln1', (4096,)) == P(None)
# MoE experts on model
assert spec_of('we1', (48, 64, 2048, 1408)) == P(None, 'model', 'data', None)

# batch: leading dim on (pod+)data
b = jax.ShapeDtypeStruct((256, 4096), jnp.int32)
assert batch_spec(b, mesh) == P('data', None)
# mrope positions: (3, B, S)
m = jax.ShapeDtypeStruct((3, 256, 4096), jnp.int32)
assert batch_spec(m, mesh) == P(None, 'data', None)
# batch=1 replicates instead of failing
b1 = jax.ShapeDtypeStruct((1, 524288), jnp.int32)
assert batch_spec(b1, mesh) == P(None, None)

# caches
cfg = get_config('qwen2_vl_72b')   # kv=8 (non-divisible), head_dim=128
kv = jax.ShapeDtypeStruct((80, 128, 32768, 8, 128), jnp.bfloat16)
s = cache_spec(kv, cfg, mesh, batch=128)
assert s == P(None, 'data', None, None, 'model'), s  # head_dim fallback
cfg2 = get_config('gemma3_27b')    # kv=16 divisible
kv2 = jax.ShapeDtypeStruct((10, 128, 32768, 16, 128), jnp.bfloat16)
s2 = cache_spec(kv2, cfg2, mesh, batch=128)
assert s2 == P(None, 'data', None, 'model', None), s2
cfg3 = get_config('mamba2_370m')
ssm = jax.ShapeDtypeStruct((48, 128, 32, 64, 128), jnp.float32)
s3 = cache_spec(ssm, cfg3, mesh, batch=128)
assert s3 == P(None, 'data', 'model', None, None), s3
print('sharding specs ok')
""", n_devices=512)


def test_per_shard_k_budget():
    """Per-shard top-k budgets preserve the global budget to rounding
    (pure logic, no devices)."""
    from repro.core.topk import global_k, per_shard_k

    for n, frac, t in [(100_000, 0.01, 4), (16384, 0.05, 2), (999, 1.0, 4),
                       (65536, 0.001, 8)]:
        k = global_k(n, frac)
        ks = per_shard_k(n, frac, t)
        assert k <= ks * t <= k + t - 1, (n, frac, t, k, ks)
    # full k: the budget covers the padded shard length, so sharded
    # selection stays lossless
    assert per_shard_k(10, 1.0, 4) == 3   # == ceil(10/4) == shard length
    assert per_shard_k(8, 1.0, 2) == 4
    # never zero, degenerate single shard == unsharded budget
    assert per_shard_k(100, 1e-6, 8) == 1
    assert per_shard_k(1000, 0.01, 1) == global_k(1000, 0.01)


def test_ef_specs_dp_and_2d(multidevice):
    multidevice(r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.sharding.params import ef_spec, ef_shardings
from repro.train import init_ef_state

from repro.compat import make_mesh
mesh = make_mesh((4, 2), ('data', 'model'))
sd = jax.ShapeDtypeStruct
# DP-only layout (P, size): worker dim over data
assert ef_spec(sd((4, 1000), jnp.float32), mesh) == P('data', None)
# DP x TP layout (D, T, shard_len): (worker, model shard) over (data, model)
assert ef_spec(sd((4, 2, 500), jnp.float32), mesh) == P('data', 'model', None)
# non-divisible dims drop their axis instead of failing to lower
assert ef_spec(sd((3, 1000), jnp.float32), mesh) == P(None, None)

# init_ef_state per-shard layout: (D, T, ceil(size/T)), odd sizes padded
params = {'w': jnp.zeros((7, 3)), 'b': jnp.zeros((5,))}
ef = init_ef_state(params, 4, model_shards=2)
assert ef['w'].shape == (4, 2, 11)   # ceil(21/2)
assert ef['b'].shape == (4, 2, 3)    # ceil(5/2)
sh = ef_shardings(ef, mesh)
assert sh['w'].spec == P('data', 'model', None)
# DP-only layout unchanged
ef1 = init_ef_state(params, 4)
assert ef1['w'].shape == (4, 21)
assert ef_shardings(ef1, mesh)['w'].spec == P('data', None)
print('ef specs ok')
""", n_devices=8)


def test_multipod_dp_axes(multidevice):
    multidevice(r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_production_mesh
from repro.sharding.params import param_spec, batch_spec
DK = jax.tree_util.DictKey
mesh = make_production_mesh(multi_pod=True)
leaf = jax.ShapeDtypeStruct((8192, 8192), jnp.float32)
s = param_spec((DK('wq'),), leaf, mesh)
assert s == P(('pod', 'data'), 'model'), s  # fsdp composes with pod
b = jax.ShapeDtypeStruct((256, 4096), jnp.int32)
assert batch_spec(b, mesh) == P(('pod', 'data'), None)
print('multipod specs ok')
""", n_devices=512)
