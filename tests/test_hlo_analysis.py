"""The roofline analyzer must multiply while-loop bodies by trip count —
XLA's own cost_analysis does not (this test documents both facts)."""
import jax
import jax.numpy as jnp

from repro.compat import cost_analysis_dict
from repro.launch.hlo_analysis import ModuleAnalyzer


def _compile(fn, *sds):
    return jax.jit(fn).lower(*sds).compile()


def test_xla_cost_analysis_ignores_trip_count():
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def one(x, w):
        return x @ w

    def ten(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    f1 = cost_analysis_dict(_compile(one, x, w))["flops"]
    f10 = cost_analysis_dict(_compile(ten, x, w))["flops"]
    assert f10 / f1 < 2.0  # body counted once: the bug we work around


def test_analyzer_multiplies_trip_count():
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    matmul_flops = 2 * 256**3

    def one(x, w):
        return x @ w

    def ten(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    c1 = ModuleAnalyzer(_compile(one, x, w).as_text()).cost()
    c10 = ModuleAnalyzer(_compile(ten, x, w).as_text()).cost()
    assert abs(c1.flops - matmul_flops) / matmul_flops < 0.05, c1.flops
    assert abs(c10.flops - 10 * matmul_flops) / (10 * matmul_flops) < 0.05
    # bytes also scale with trips (x and w streamed per iteration)
    assert c10.bytes > 5 * c1.bytes


def test_analyzer_nested_scan():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def nested(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=3)
        return out

    c = ModuleAnalyzer(_compile(nested, x, w).as_text()).cost()
    expect = 12 * 2 * 128**3
    assert abs(c.flops - expect) / expect < 0.1, c.flops


def test_analyzer_counts_collectives(multidevice):
    out = multidevice(r"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_analysis import ModuleAnalyzer

from repro.compat import make_mesh
mesh = make_mesh((8,), ('data',))
x = jax.ShapeDtypeStruct((8, 1024), jnp.float32)

def f(x):
    return jax.lax.with_sharding_constraint(
        x.sum(0), NamedSharding(mesh, P()))  # all-reduce

sh = NamedSharding(mesh, P('data', None))
comp = jax.jit(f, in_shardings=(sh,)).lower(x).compile()
c = ModuleAnalyzer(comp.as_text()).cost()
print('AR_BYTES', int(sum(c.coll.values())))
""")
    bytes_ = int(out.strip().split("AR_BYTES")[1])
    assert bytes_ >= 1024 * 4  # at least one 4KiB all-reduce operand


def test_peaks_refuse_an_unknown_device_kind():
    """Roofline terms use v5e peaks; a TPU of another kind is refused, while
    CPU placeholders (the dry run's stand-ins for a v5e mesh) pass."""
    from types import SimpleNamespace

    import pytest

    from repro.launch.hlo_analysis import Roofline, check_device
    check_device(SimpleNamespace(platform="tpu", device_kind="TPU v5 lite"))
    check_device(SimpleNamespace(platform="cpu", device_kind="cpu"))
    with pytest.raises(ValueError, match="TPU v4"):
        check_device(SimpleNamespace(platform="tpu", device_kind="TPU v4"))
    roof = Roofline(flops=197e12, hbm_bytes=0.0, coll_bytes=0.0,
                    coll_by_kind={}, coll_counts={}, xla_flops_once=0.0,
                    arg_bytes=0, out_bytes=0, temp_bytes=0)
    assert roof.t_compute == 1.0 and roof.bottleneck == "compute"
