"""Training substrate: optimizer, checkpoint/restore, elastic reshard,
supervisor fault tolerance, data determinism."""
import os
import queue
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (AsyncCheckpointer, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.data import make_batch
from repro.models import build_model
from repro.models.common import ModelConfig, ShapeConfig
from repro.optim import (adamw_init, adamw_update, clip_by_global_norm,
                         cosine_schedule)
from repro.runtime import FailureInjector, StragglerMonitor, Supervisor
from repro.train import TrainHParams, make_train_step

CFG = ModelConfig(arch_id="sub", family="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                  compute_dtype="float32")
SHAPE = ShapeConfig("s", "train", 16, 2)


def test_adamw_descends_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0])}
    opt = adamw_init(params)
    for _ in range(200):
        g = {"w": 2 * params["w"]}  # grad of |w|^2
        params, opt, _ = adamw_update(params, g, opt, lr=0.05,
                                      weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_clip_by_global_norm():
    g = {"a": jnp.ones((10,)) * 100.0}
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert float(gn) == pytest.approx(np.sqrt(10) * 100, rel=1e-5)
    norm = float(jnp.sqrt(jnp.sum(clipped["a"] ** 2)))
    assert norm == pytest.approx(1.0, rel=1e-5)


def test_cosine_schedule_shape():
    lr0 = float(cosine_schedule(jnp.asarray(0), peak_lr=1.0, warmup=10, total=100))
    lrp = float(cosine_schedule(jnp.asarray(10), peak_lr=1.0, warmup=10, total=100))
    lre = float(cosine_schedule(jnp.asarray(100), peak_lr=1.0, warmup=10, total=100))
    assert lr0 == pytest.approx(0.0)
    assert lrp == pytest.approx(1.0)
    assert lre == pytest.approx(0.1, rel=1e-3)  # floor


def test_checkpoint_roundtrip(tmp_path):
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    state = {"params": params, "opt": opt}
    save_checkpoint(str(tmp_path), 7, state)
    assert latest_step(str(tmp_path)) == 7
    restored = restore_checkpoint(str(tmp_path), 7, state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_incomplete_invisible(tmp_path):
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path), 3, params)
    # simulate a crash mid-write: directory without .complete marker
    os.makedirs(tmp_path / "step_00000009")
    assert latest_step(str(tmp_path)) == 3


def test_checkpoint_atomic_on_crash(tmp_path):
    """A crash mid-write must leave only a .tmp dir — never a torn final
    checkpoint — and a clean re-save of the same step must fully recover."""
    tree = {"a": jnp.arange(6.0), "b": jnp.ones((2, 3))}
    save_checkpoint(str(tmp_path), 1, tree)

    real_save = np.save
    calls = {"n": 0}

    def crashing_save(path, arr):
        calls["n"] += 1
        if calls["n"] == 2:  # die on the second leaf
            raise OSError("disk vanished")
        real_save(path, arr)

    np.save = crashing_save
    try:
        with pytest.raises(OSError):
            save_checkpoint(str(tmp_path), 5, tree)
    finally:
        np.save = real_save

    # the crashed attempt is invisible: only the .tmp carcass exists
    assert latest_step(str(tmp_path)) == 1
    assert not os.path.isdir(tmp_path / "step_00000005")
    assert os.path.isdir(tmp_path / "step_00000005.tmp")

    # a retry replaces the carcass wholesale and restores bit-exact
    save_checkpoint(str(tmp_path), 5, tree)
    assert latest_step(str(tmp_path)) == 5
    assert not os.path.isdir(tmp_path / "step_00000005.tmp")
    restored = restore_checkpoint(str(tmp_path), 5, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_step_ignores_foreign_entries(tmp_path):
    save_checkpoint(str(tmp_path), 2, {"x": jnp.zeros(2)})
    os.makedirs(tmp_path / "step_00000009.tmp")  # crashed attempt
    os.makedirs(tmp_path / "step_junk")          # unparseable name
    assert latest_step(str(tmp_path)) == 2


def test_async_checkpointer(tmp_path):
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    ck = AsyncCheckpointer(str(tmp_path))
    ck.save(1, params)
    ck.save(2, params)
    ck.close()
    assert latest_step(str(tmp_path)) == 2


def test_async_checkpointer_drain_race(tmp_path):
    """queue.Full followed by the worker dequeuing before our get_nowait:
    the drop-stale-entry path must swallow queue.Empty, not leak it."""
    ck = AsyncCheckpointer(str(tmp_path))
    real_q = ck._q

    class RacyQueue:
        def __init__(self):
            self.full_once = True

        def put_nowait(self, item):
            if self.full_once:
                self.full_once = False
                raise queue.Full
            real_q.put_nowait(item)

        def get_nowait(self):
            raise queue.Empty  # worker beat us to the dequeue

    ck._q = RacyQueue()
    ck.save(1, {"x": jnp.ones(3)})  # must not raise queue.Empty
    ck._q = real_q
    ck.close()
    assert latest_step(str(tmp_path)) == 1


def test_save_on_signal_sigterm(tmp_path):
    """Preemption hook: SIGTERM writes a final checkpoint, then the process
    dies by the default signal disposition (so schedulers see a clean kill)."""
    code = textwrap.dedent("""
        import os, signal, sys
        import jax.numpy as jnp
        from repro.checkpoint import save_on_signal
        save_on_signal(sys.argv[1], lambda: (7, {"w": jnp.arange(4.0)}))
        os.kill(os.getpid(), signal.SIGTERM)
        print("unreachable")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
    ) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    import signal as _signal
    assert proc.returncode == -_signal.SIGTERM, proc.stderr
    assert "unreachable" not in proc.stdout
    assert latest_step(str(tmp_path)) == 7
    like = {"w": jnp.zeros(4)}
    out = restore_checkpoint(str(tmp_path), 7, like)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.arange(4.0))


def test_supervisor_recovers_from_failures(tmp_path):
    """Inject two node failures; training must reach n_steps with restarts,
    and the result must equal an uninterrupted run (deterministic data)."""
    model = build_model(CFG)
    hp = TrainHParams(ce_chunk=8, attn_chunk=8, remat=False, total_steps=50,
                      warmup=2)
    step_fn_jit = jax.jit(make_train_step(model, hp))

    def step_fn(state, step):
        params, opt = state
        batch = make_batch(CFG, SHAPE, step)
        params, opt, _ = step_fn_jit(params, opt, batch)
        return (params, opt)

    init = (model.init(jax.random.PRNGKey(0)), adamw_init(model.init(jax.random.PRNGKey(0))))
    sup = Supervisor(str(tmp_path / "ft"), ckpt_every=4, max_restarts=5,
                     injector=FailureInjector(fail_at_steps=(6, 13)))
    state, steps = sup.run(init, step_fn, n_steps=16)
    assert steps == 16
    assert sup.restarts == 2

    # uninterrupted reference
    ref = init
    for s in range(16):
        ref = step_fn(ref, s)
    for a, b in zip(jax.tree.leaves(state[0]), jax.tree.leaves(ref[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_straggler_monitor():
    mon = StragglerMonitor(window=16, threshold=2.0)
    for i in range(10):
        mon.record(i, 0.1)
    assert mon.record(10, 0.5) is True
    assert mon.record(11, 0.11) is False
    assert len(mon.flagged) == 1


def test_straggler_monitor_warmup():
    """Fewer than 8 samples: no median worth trusting, never flags —
    even a 1000x outlier during warm-up stays quiet."""
    mon = StragglerMonitor(window=16, threshold=2.0)
    for i in range(7):
        assert mon.record(i, 100.0 if i == 3 else 0.1) is False
    assert mon.flagged == []


def test_straggler_monitor_threshold_boundary():
    """The trip condition is strict: exactly threshold x median passes,
    anything beyond flags."""
    at = StragglerMonitor(window=16, threshold=2.0)
    over = StragglerMonitor(window=16, threshold=2.0)
    for i in range(8):
        at.record(i, 0.1)
        over.record(i, 0.1)
    med = sorted(at.times)[len(at.times) // 2]
    assert at.record(8, 2.0 * med) is False
    assert over.record(8, 2.0 * med * 1.01) is True
    assert over.flagged[0][0] == 8


def test_data_determinism():
    b1 = make_batch(CFG, SHAPE, step=5)
    b2 = make_batch(CFG, SHAPE, step=5)
    b3 = make_batch(CFG, SHAPE, step=6)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b3["tokens"]))


def test_elastic_reshard_multidevice(multidevice):
    """Save on a 1×8 mesh, restore onto 2×4 and 8×1 — elastic scaling."""
    multidevice(r"""
import jax, jax.numpy as jnp, numpy as np, tempfile, os
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import save_checkpoint, restore_checkpoint

tmp = tempfile.mkdtemp()
from repro.compat import make_mesh
mesh_a = make_mesh((8,), ('data',))
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
xa = jax.device_put(x, NamedSharding(mesh_a, P('data')))
save_checkpoint(tmp, 1, {'x': xa})

mesh_b = make_mesh((2, 4), ('data', 'model'))
sh = {'x': NamedSharding(mesh_b, P('data', 'model'))}
out = restore_checkpoint(tmp, 1, {'x': x}, sh)
np.testing.assert_array_equal(np.asarray(out['x']), np.asarray(x))
assert out['x'].sharding.spec == P('data', 'model')
print('elastic ok')
""", n_devices=8)
