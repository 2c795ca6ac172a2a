"""CPU-only tests of the benchmark: ``python -m pytest chipbench/tests``.

They never touch an accelerator: JAX is held to the CPU, and compiled
programs go to a temporary cache, not the checkout's.
"""
import json
import os
import subprocess
import sys
import tempfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="chipbench_cache_"))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: toy traffic per cell, small enough for the Pallas interpreter
TOY = {
    "er.vec": {"m": 64, "n": 64, "k": 8, "nnz_per_matrix": 128},
    "er.hash": {"m": 128, "n": 128, "k": 4, "nnz_per_matrix": 64},
    "rmat.hash": {"m": 64, "n": 64, "k": 8, "nnz_per_matrix": 128},
    "summa.2x2": {"n": 64, "density": 0.05},
}


SUMMA_SCRIPT = r"""
import json, os, sys
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
import jax, jax.numpy as jnp
from chipbench import run
from chipbench.drivers import summa
from repro.core import spgemm

toy = {toy!r}
def once(system=None):
    r = run.run("summa.2x2", 2**31 + 9, 0.2, False, system=system,
                traffic=toy, require_accelerator=False,
                log=open(os.devnull, "w"))
    return r

results = {{"sound": once()}}
config = run.cell_spec("summa.2x2").config
results["control"] = once(summa.control(config))

real_run, real_gather = spgemm._spkadd_run, jax.lax.all_gather

def half_batch(partials, algorithm):
    kept = [p._replace(vals=p.vals * 2) for p in partials[: len(partials) // 2]]
    return real_run(kept, algorithm=algorithm)

def answer_altered(partials, algorithm):
    out = real_run(partials, algorithm=algorithm)
    return out._replace(vals=out.vals.at[0].add(1.0))

def no_exchange(x, axis_name, *, axis=0, tiled=False, **kw):
    return jnp.concatenate([x] * 2, axis=axis)

def state_unchanged(a, b, mesh, algorithm):
    return a

results["state_unchanged"] = once(state_unchanged)
for name, target, attr, fake in [
        ("half_batch", spgemm, "_spkadd_run", half_batch),
        ("answer_altered", spgemm, "_spkadd_run", answer_altered),
        ("exchange_left_out", jax.lax, "all_gather", no_exchange)]:
    saved = getattr(target, attr)
    setattr(target, attr, fake)
    try:
        results[name] = once()
    finally:
        setattr(target, attr, saved)
print(json.dumps(results))
"""


@pytest.fixture(scope="session")
def summa_results():
    """The SUMMA cell at toy traffic on four virtual CPU devices, in a
    process of its own (JAX fixes the device count when it starts): the
    sound program, the control, and each fault planted underneath."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SUMMA_SCRIPT.format(root=ROOT, toy=TOY["summa.2x2"])
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
