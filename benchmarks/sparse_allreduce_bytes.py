"""The DL integration (paper §I): collective bytes of dense vs sparse
gradient allreduce, from lowered HLO on a fake-device mesh.

Reports per-device collective traffic for (a) dense all-reduce training and
(b) top-k + SpKAdd sparse allreduce at several sparsity levels and all three
schedules. This is the communication-side claim of sparse allreduce: traffic
∝ P·s instead of 2·D, a win while k_fraction ≲ 2/(P·expansion). Also
wall-times one step of each on the fake devices.

``--mesh DxT`` with T > 1 measures the sparse-DP × TP composition
(DESIGN.md §8): dense model-axis combine + per-shard sparse data-axis
reduction + model-axis gather. ``--smoke`` shrinks the model and fraction
grid to the CI gate size and sweeps both a 1-D and a 2-D mesh; ``--json``
writes the emitted records as a ``BENCH_*.json`` artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import parse_emit_lines, write_json

SNIPPET = r"""
import json, sys, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.common import ModelConfig, ShapeConfig
from repro.models import build_model
from repro.train import (make_train_step, make_compressed_train_step,
                         init_ef_state, TrainHParams)
from repro.sharding.params import ef_shardings
from repro.optim import adamw_init
from repro.data import make_batch
from repro.launch.hlo_analysis import ModuleAnalyzer
from repro.compat import make_mesh

knobs = json.loads(sys.argv[1])
D, T = knobs['mesh']
cfg = ModelConfig(arch_id='bench', family='dense', n_layers=knobs['layers'],
                  d_model=knobs['d_model'], n_heads=8, n_kv_heads=8,
                  d_ff=knobs['d_ff'], vocab=knobs['vocab'],
                  compute_dtype='float32')
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
n_params = sum(x.size for x in jax.tree.leaves(params))
opt = adamw_init(params)
hp = TrainHParams(ce_chunk=64, attn_chunk=64, remat=False,
                  total_steps=100, warmup=5)
shape = ShapeConfig('b', 'train', knobs['seq'], knobs['batch'])
batch = make_batch(cfg, shape, 0)
if T > 1:
    mesh = make_mesh((D, T), ('data', 'model'))
    baxes, tag = ('data', 'model'), f'allreduce_{D}x{T}'
else:
    mesh = make_mesh((D,), ('data',))
    baxes, tag = 'data', 'allreduce'

bsh = jax.tree.map(
    lambda x: jax.device_put(x, NamedSharding(mesh, P(baxes))), batch)
dense_step = jax.jit(make_train_step(model, hp))
comp = dense_step.lower(params, opt, bsh).compile()
c = ModuleAnalyzer(comp.as_text()).cost()
print(f"{tag}/dense/coll_bytes,{sum(c.coll.values()):.0f},params={n_params}")
jax.block_until_ready(dense_step(params, opt, bsh)); t0 = time.perf_counter()
jax.block_until_ready(dense_step(params, opt, bsh))
print(f"{tag}/dense/step,{(time.perf_counter()-t0)*1e6:.1f},wall")

for frac in knobs['fracs']:
    for sched in knobs['scheds']:
        ef = init_ef_state(params, D, model_shards=T)
        ef = jax.tree.map(jax.device_put, ef, ef_shardings(ef, mesh))
        cstep = jax.jit(make_compressed_train_step(
            model, mesh, hp, k_fraction=frac, schedule=sched,
            min_compress_elems=knobs['min_compress_elems']))
        comp = cstep.lower(params, opt, ef, bsh).compile()
        c = ModuleAnalyzer(comp.as_text()).cost()
        print(f"{tag}/topk{frac}/{sched}/coll_bytes,"
              f"{sum(c.coll.values()):.0f},")
        out = cstep(params, opt, ef, bsh); jax.block_until_ready(out)
        t0 = time.perf_counter()
        jax.block_until_ready(cstep(params, opt, ef, bsh))
        print(f"{tag}/topk{frac}/{sched}/step,"
              f"{(time.perf_counter()-t0)*1e6:.1f},wall")
"""

FULL_KNOBS = dict(layers=4, d_model=512, d_ff=2048, vocab=8192,
                  batch=128, seq=16, fracs=(0.01, 0.05),
                  scheds=("gather_kway", "tree_2way", "ring_2way"),
                  min_compress_elems=16384)
SMOKE_KNOBS = dict(layers=2, d_model=128, d_ff=256, vocab=512,
                   batch=32, seq=8, fracs=(0.05,),
                   scheds=("gather_kway", "tree_2way", "ring_2way"),
                   min_compress_elems=4096)


def run_mesh(mesh: tuple[int, int], knobs: dict) -> list[dict]:
    """Fork a child with D*T fake devices and collect its emitted records."""
    from repro.compat import backend_initialized
    if backend_initialized():
        raise RuntimeError("start this bench before anything in the process "
                           "initialises jax: its child needs the devices")
    d, t = mesh
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d * t}"
    src = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    payload = json.dumps({**knobs, "mesh": [d, t]})
    out = subprocess.run([sys.executable, "-c", SNIPPET, payload], env=env,
                         capture_output=True, text=True, timeout=1800)
    sys.stdout.write(out.stdout)
    if out.returncode != 0:
        sys.stderr.write(out.stderr)
        raise SystemExit("sparse_allreduce subprocess failed")
    return parse_emit_lines(out.stdout)


def parse_mesh(spec: str) -> tuple[int, int]:
    if "x" in spec:
        d, t = (int(x) for x in spec.split("x"))
        return d, t
    return int(spec), 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="8",
                    help="'D' (DP-only) or 'DxT' (sparse-DP × TP)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: tiny model, one fraction, both a 1-D "
                         "and a 2-D mesh")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write records as a BENCH_*.json artifact")
    args = ap.parse_args()

    records = []
    if args.smoke:
        for mesh in ((8, 1), (4, 2)):
            records += run_mesh(mesh, SMOKE_KNOBS)
    else:
        records += run_mesh(parse_mesh(args.mesh), FULL_KNOBS)
    if args.json:
        write_json(args.json, records=records,
                   suite="sparse_allreduce_smoke" if args.smoke
                   else "sparse_allreduce")


if __name__ == "__main__":
    main()
