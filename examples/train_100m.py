"""End-to-end driver: train a ~100M-param LM for a few hundred steps on CPU,
with checkpoint/restart fault tolerance and optional top-k sparse-allreduce
gradient compression (the paper's technique) on a DP mesh.

Run (dense DP):        PYTHONPATH=src python examples/train_100m.py --steps 200
Run (paper technique): XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python examples/train_100m.py --steps 50 --compress \
    --schedule gather_kway --k-fraction 0.05
Resume after a crash:  re-run the same command; the Supervisor restores the
latest complete checkpoint automatically.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step
from repro.data import make_batch
from repro.models import build_model
from repro.models.common import ModelConfig, ShapeConfig
from repro.optim import adamw_init
from repro.runtime import Supervisor
from repro.train import (TrainHParams, init_ef_state, make_train_step,
                         make_compressed_train_step)

# ~100M params: 12L × d768 (GPT-2-small-ish with SwiGLU + GQA)
CFG = ModelConfig(arch_id="repro-100m", family="dense", n_layers=12,
                  d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
                  vocab=32000, compute_dtype="float32")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_100m_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress", action="store_true",
                    help="top-k + SpKAdd sparse allreduce over the data axis")
    ap.add_argument("--schedule", default="gather_kway",
                    choices=["gather_kway", "tree_2way", "ring_2way"])
    ap.add_argument("--k-fraction", type=float, default=0.05)
    args = ap.parse_args()

    model = build_model(CFG)
    n_params = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))))
    print(f"model: {CFG.arch_id}, {n_params/1e6:.1f}M params")
    shape = ShapeConfig("train", "train", args.seq, args.batch)
    hp = TrainHParams(ce_chunk=max(32, args.seq // 8),
                      attn_chunk=max(64, args.seq // 4),
                      remat=True, total_steps=args.steps, warmup=20)

    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)

    if args.compress:
        n_dev = len(jax.devices())
        assert n_dev > 1, ("--compress needs a DP mesh: set XLA_FLAGS="
                           "--xla_force_host_platform_device_count=4")
        from repro.compat import make_mesh
        mesh = make_mesh((n_dev,), ("data",))
        step_impl = jax.jit(make_compressed_train_step(
            model, mesh, hp, k_fraction=args.k_fraction,
            schedule=args.schedule))
        ef = init_ef_state(params, n_dev)
        state0 = (params, opt, ef)

        def step_fn(state, step):
            from jax.sharding import NamedSharding, PartitionSpec as P
            p, o, e = state
            batch = make_batch(CFG, shape, step)
            batch = jax.tree.map(lambda x: jax.device_put(
                x, NamedSharding(mesh, P(*(("data",) + (None,) * (x.ndim - 1))))),
                batch)
            p, o, e, metrics = step_impl(p, o, e, batch)
            if step % 10 == 0:
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"[sparse-allreduce/{args.schedule}]", flush=True)
            return (p, o, e)
    else:
        step_impl = jax.jit(make_train_step(model, hp))
        state0 = (params, opt)

        def step_fn(state, step):
            p, o = state
            batch = make_batch(CFG, shape, step)
            p, o, metrics = step_impl(p, o, batch)
            if step % 10 == 0:
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)
            return (p, o)

    resumed = latest_step(args.ckpt_dir)
    if resumed:
        print(f"resuming from checkpoint step {resumed}")
    sup = Supervisor(args.ckpt_dir, ckpt_every=args.ckpt_every,
                     async_ckpt=True)
    t0 = time.time()
    state, steps = sup.run(state0, step_fn, args.steps)
    dt = time.time() - t0
    print(f"done: {steps} steps in {dt:.1f}s "
          f"({dt / max(1, steps - (resumed or 0)):.2f}s/step)")
    if sup.monitor.flagged:
        print(f"stragglers flagged: {sup.monitor.flagged}")


if __name__ == "__main__":
    main()
