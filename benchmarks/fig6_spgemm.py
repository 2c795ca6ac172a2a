"""Fig. 6 analogue: SpKAdd's impact inside distributed SpGEMM (sparse SUMMA).

Spawns a 4-device (2×2 process grid) subprocess and times the full SUMMA with
the reduction step implemented by each SpKAdd algorithm. The paper's result:
swapping heap→hash reduction makes the computation ≥2× faster at scale; here
the incremental (2-way) reduction plays the slow baseline.
"""
from __future__ import annotations

import os
import subprocess
import sys

SNIPPET = r"""
import functools, time
import jax, jax.numpy as jnp, numpy as np
from repro.core.spgemm import spgemm_summa

from repro.compat import make_mesh
mesh = make_mesh((2, 2), ('data', 'model'))
rng = np.random.default_rng(0)
M, K, N = 512, 512, 256
def sprand(m, n, frac=0.05):
    d = np.zeros((m, n), np.float32)
    nz = int(m*n*frac)
    idx = rng.choice(m*n, nz, replace=False)
    d.flat[idx] = rng.standard_normal(nz)
    return jnp.asarray(d)
A, B = sprand(M, K), sprand(K, N)
for alg in ['incremental', 'tree', 'sorted', 'spa']:
    fn = jax.jit(functools.partial(spgemm_summa, mesh=mesh, algorithm=alg,
                                   partial_cap_per_stage=int(M*N*0.1/4)))
    out = fn(A, B); jax.block_until_ready(out)
    ts = []
    for _ in range(3):
        t0 = time.perf_counter(); jax.block_until_ready(fn(A, B))
        ts.append(time.perf_counter() - t0)
    print(f"fig6/summa_reduction={alg},{np.median(ts)*1e6:.1f},2x2grid")
"""


def main():
    from repro.compat import backend_initialized
    if backend_initialized():
        raise RuntimeError("start this bench before anything in the process "
                           "initialises jax: its child needs the devices")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", SNIPPET], env=env,
                         capture_output=True, text=True, timeout=900)
    sys.stdout.write(out.stdout)
    if out.returncode != 0:
        sys.stderr.write(out.stderr)
        raise SystemExit("fig6 subprocess failed")


if __name__ == "__main__":
    main()
