#!/usr/bin/env python3
"""Drive the SpKAdd engine's main path once on a TPU and check what it returns.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the four-chip phases only

One chip, through the entry points a user calls (``spkadd_auto``,
``spkadd_batched``, the stream service):

- **H**: ER, m = n = 16,384, k = 32, 16,384 nonzeros per matrix (524,288
  inputs). The engine must pick the sort-free ``hash`` regime, on one
  2^20-slot table.
- **V**: ER, m = n = 8,192, k = 64, 65,536 nonzeros per matrix (4,194,304
  inputs, a 256 MiB dense accumulator). The engine must pick ``vec``; then
  every canonical regime runs forced, and ``spkadd_batched`` adds B = 4
  such collections in one program.
- **D**: duplicate-heavy, the stream service's tenant shape (64 x 16, 1,024
  slots) with k = 64 and 256 nonzeros per matrix: every key is added 3 to
  ~30 times, so any regime that adds duplicates out of stream order breaks
  bit-identity. The engine must pick ``spa``; every canonical regime runs
  forced, and ``spkadd_batched`` adds B = 4 such collections.
- **O**: a 64 x 64 collection forced through ``vec``, whose 4,096-slot tile
  takes the one-hot MXU fold (V's tiles take the serial fold).
- **S**: ``launch/stream_serve.py`` at its README settings (32 tenants,
  10 simulated seconds, 4 pushes per tenant per second).

Every engine result is checked against numpy over the generated triples
(``np.add.at``: exact key set, ``allclose`` values) and bit for bit against
the ``sorted`` regime; each stream tenant's flushed sum against numpy over
the pushes the service admitted. Four chips: SUMMA on a 2 x 2 mesh against
``spgemm_reference`` (A, B 8,192^2 f32 at 1% density), and one compressed
data-parallel training step of smollm-135m (full width, depth cut to 2
layers) at k-fraction 1.0 against the dense step.

Each phase prints the regime it dispatched, its wall time after
``block_until_ready`` (compilation excluded, reported apart) and whether
its compiled HLO holds a Mosaic kernel (``tpu_custom_call``). The script
exits non-zero, printing no result, when jax finds no TPU or any check
fails; its last line is one JSON object naming the device. Compiled
programs are cached in ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else
in ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

#: regimes whose TPU path is a Mosaic kernel
PALLAS_REGIMES = ("hash", "vec", "blocked_spa")


class SmokeFailure(AssertionError):
    pass


def needs_kernel(regime: str) -> bool:
    """On a TPU a Pallas regime must compile to a Mosaic kernel (on the CPU
    its kernels run under the interpreter and leave no custom call)."""
    from repro.compat import interpret_kernels
    return regime in PALLAS_REGIMES and not interpret_kernels()


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> dict:
    print(f"phase {phase}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)
    return {"phase": phase, **fields}


# ---------------------------------------------------------------------------
# data and references
# ---------------------------------------------------------------------------

def er_triples(rng, m: int, n: int, k: int, nnz: int):
    """k Erdos-Renyi matrices of ``nnz`` uniformly placed nonzeros each, as
    CSC keys (``col * m + row``) and standard-normal f32 values."""
    keys = rng.integers(0, m * n, size=(k, nnz), dtype=np.int64)
    vals = rng.standard_normal((k, nnz), dtype=np.float32)
    return keys.astype(np.int32), vals


def collection(keys, vals, shape):
    import jax.numpy as jnp
    from repro.core.sparse import PaddedCOO
    return [PaddedCOO(jnp.asarray(k), jnp.asarray(v),
                      jnp.asarray(len(k), jnp.int32), shape)
            for k, v in zip(keys, vals)]


def numpy_sum(keys, vals):
    """Distinct keys (ascending) and their f32 sums, added in stream order."""
    uniq, inv = np.unique(keys.reshape(-1), return_inverse=True)
    sums = np.zeros(len(uniq), np.float32)
    np.add.at(sums, inv, vals.reshape(-1))
    return uniq, sums


def check_result(name: str, out, want, sorted_out=None) -> None:
    """``out`` holds exactly numpy's key set with allclose values, and (when
    given) equals the ``sorted`` regime's PaddedCOO bit for bit."""
    uniq, sums = want
    keys, vals, nnz = np.asarray(out.keys), np.asarray(out.vals), int(out.nnz)
    check(nnz == len(uniq), f"{name}: nnz {nnz} != numpy {len(uniq)}")
    check(np.array_equal(keys[:nnz], uniq), f"{name}: key set differs")
    check(np.allclose(vals[:nnz], sums, rtol=1e-5, atol=1e-5),
          f"{name}: values differ from numpy (max |err| "
          f"{np.abs(vals[:nnz] - sums).max()})")
    if sorted_out is not None:
        same = (nnz == int(sorted_out.nnz)
                and np.array_equal(keys, np.asarray(sorted_out.keys))
                and np.array_equal(vals, np.asarray(sorted_out.vals)))
        check(same, f"{name}: not bit-identical to the sorted regime")


def run_compiled(fn, *args):
    """Compile ``fn`` for ``args``, run it once to warm up, then time one
    call up to ``block_until_ready``. Returns (out, wall_s, compile_s,
    whether the compiled HLO holds a Mosaic kernel)."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    kernel = "tpu_custom_call" in compiled.as_text()
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0, compile_s, kernel


def _timing(wall_s: float, compile_s: float) -> dict:
    return {"wall_s": wall_s, "compile_s": compile_s}


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_engine(name: str, *, m: int, n: int, k: int, nnz: int,
                 expect: str | None, forced=(), batch: int = 0,
                 seed: int = 0) -> list:
    """``spkadd_auto`` on one ER collection (its regime must be ``expect``),
    then each ``forced`` regime, then ``spkadd_batched`` over ``batch``
    collections when ``batch`` > 1."""
    import jax
    from repro.core import engine as E
    from repro.kernels import ops as kops

    rng = np.random.default_rng(seed)
    keys, vals = er_triples(rng, m, n, k, nnz)
    mats = collection(keys, vals, (m, n))
    want = numpy_sum(keys, vals)
    records = []

    sorted_fn = jax.jit(E._CANONICAL["sorted"])
    ref, wall, comp, kernel = run_compiled(sorted_fn, mats)
    check_result(f"{name}/sorted", ref, want)
    # whether the sorted regime's sums are numpy's stream-order f32 left
    # fold bit for bit (the order every Pallas fold adds in)
    left_fold = np.array_equal(np.asarray(ref.vals)[:int(ref.nnz)], want[1])
    records.append(report(f"{name}/sorted", regime="sorted",
                          left_fold_exact=left_fold, tpu_custom_call=kernel,
                          **_timing(wall, comp)))

    _, regime = E.explain_dispatch(mats)
    check(expect is None or regime == expect,
          f"{name}: engine dispatched {regime!r}, expected {expect!r}")
    geometry = {}
    if regime == "hash":
        g = kops.hash_launch_geometry(k * nnz, m=m, n=n)
        geometry = {"table_slots": g.table_size, "parts": g.parts}
    elif regime in ("vec", "blocked_spa"):
        g = kops.partitioned_launch_geometry(k * nnz, m=m, n=n)
        geometry = {"part_elems": g.part_elems, "parts": g.parts}
    out, wall, comp, kernel = run_compiled(E.spkadd_auto, mats)
    check_result(f"{name}/auto", out, want, ref)
    check(kernel or not needs_kernel(regime),
          f"{name}: {regime} ran without a Mosaic kernel")
    records.append(report(f"{name}/auto", regime=regime, inputs=k * nnz,
                          **geometry, tpu_custom_call=kernel,
                          **_timing(wall, comp)))

    for forced_regime in forced:
        out, wall, comp, kernel = run_compiled(E._CANONICAL[forced_regime],
                                               mats)
        check_result(f"{name}/{forced_regime}", out, want, ref)
        check(kernel or not needs_kernel(forced_regime),
              f"{name}: {forced_regime} ran without a Mosaic kernel")
        records.append(report(f"{name}/forced", regime=forced_regime,
                              tpu_custom_call=kernel, **_timing(wall, comp)))

    if batch > 1:
        colls = [(keys, vals)] + [er_triples(rng, m, n, k, nnz)
                                  for _ in range(batch - 1)]
        stacked = E.stack_collections([collection(kk, vv, (m, n))
                                       for kk, vv in colls])
        _, _, regime = E.explain_batched_dispatch(stacked)
        out, wall, comp, kernel = run_compiled(E.spkadd_batched, stacked)
        for b, (kk, vv) in enumerate(colls):
            check_result(f"{name}/batched[{b}]",
                         E.unstack_collection([out], b)[0], numpy_sum(kk, vv),
                         sorted_fn(collection(kk, vv, (m, n))))
        check(kernel or not needs_kernel(regime),
              f"{name}: batched {regime} ran without a Mosaic kernel")
        records.append(report(f"{name}/batched", regime=regime, batch=batch,
                              inputs=batch * k * nnz, tpu_custom_call=kernel,
                              **_timing(wall, comp)))
    return records


def phase_onehot(*, m: int = 64, n: int = 64, k: int = 8, nnz: int = 256,
                 seed: int = 1) -> list:
    """``vec`` on a collection whose whole accumulator is one tile small
    enough for the one-hot fold."""
    from repro import obs
    from repro.core import engine as E

    rng = np.random.default_rng(seed)
    keys, vals = er_triples(rng, m, n, k, nnz)
    mats = collection(keys, vals, (m, n))
    want = numpy_sum(keys, vals)
    ref = run_compiled(E._CANONICAL["sorted"], mats)[0]
    folds_before = obs.counter("engine.partitioned.fold.onehot").value
    out, wall, comp, kernel = run_compiled(E._CANONICAL["vec"], mats)
    check(obs.counter("engine.partitioned.fold.onehot").value > folds_before,
          "O: vec did not take the one-hot fold")
    check_result("O/vec", out, want, ref)
    check(kernel or not needs_kernel("vec"),
          "O: vec ran without a Mosaic kernel")
    return [report("O/forced", regime="vec", fold="onehot",
                   tpu_custom_call=kernel, **_timing(wall, comp))]


def phase_stream(*, tenants: int = 32, duration: float = 10.0,
                 rate: float = 4.0, shape=(64, 16), nnz: int = 32,
                 batch_k: int = 4, cap: int = 1024, deadline: float = 0.5,
                 tick_every: float = 0.25, seed: int = 0) -> list:
    """The stream service as ``launch/stream_serve.py`` builds and drives it
    (no overload); every tenant's flushed sum must equal numpy's sum of the
    pushes the service admitted."""
    import jax
    from repro.core import engine as E
    from repro.core.stream_service import StreamService
    from repro.launch import stream_serve as SS

    offered = tenants * rate * nnz * deadline
    soft = int(4 * offered) + nnz
    service = StreamService(soft_pending_nnz=soft, hard_pending_nnz=2 * soft,
                            flush_deadline=deadline)
    names = [SS.tenant_name(i) for i in range(tenants)]
    for t in names:
        service.register_tenant(t, shape, cap_budget=cap, batch_k=batch_k)
    events = SS.build_workload(n_tenants=tenants, duration=duration,
                               rate=rate, tick_every=tick_every, seed=seed)
    t0 = time.perf_counter()
    result = SS.drive(service, events,
                      make_mat=lambda a: SS.make_matrix(shape, nnz,
                                                        a.mat_seed),
                      keep_verdicts=True)
    service.drain(duration)
    sums = {t: jax.block_until_ready(service.value(t)) for t in names}
    wall = time.perf_counter() - t0

    check(result.completed and result.admitted > 0,
          "S: the run stopped or admitted nothing")
    stats = service.stats()
    check(all(s["evicted_nnz"] == 0 for s in stats["tenants"].values()),
          "S: the service shed load at the uncongested settings")
    want = {t: np.zeros(shape, np.float32) for t in names}
    pushes = [e.arrival for e in events if e.kind == "push"]
    for arrival, verdict in zip(pushes, result.verdicts):
        if verdict.admitted:
            r = np.random.default_rng(arrival.mat_seed)
            dense = np.zeros(shape, np.float32)
            idx = r.choice(shape[0] * shape[1], size=nnz, replace=False)
            dense.flat[idx] = r.standard_normal(nnz)
            want[arrival.tenant] += dense
    for t in names:
        got = np.asarray(sums[t].to_dense())
        check(np.allclose(got, want[t], rtol=1e-5, atol=1e-5),
              f"S: tenant {t}'s flushed sum differs from numpy")

    # one flush program as the service runs it, compiled to read its HLO
    probe = E.stack_collections([[sums[names[0]]] + [
        SS.make_matrix(shape, nnz, s) for s in range(batch_k)]])
    _, _, regime = E.explain_batched_dispatch(probe)
    hlo = jax.jit(E.spkadd_batched).lower(probe).compile().as_text()
    return [report("S", regime=regime, tenants=tenants, pushes=len(pushes),
                   admitted=result.admitted, flushes=stats["flushes"],
                   tpu_custom_call="tpu_custom_call" in hlo, wall_s=wall)]


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------

def sparse_dense_matrix(rng, n: int, density: float) -> np.ndarray:
    a = np.zeros((n, n), np.float32)
    nz = int(n * n * density)
    a.flat[rng.integers(0, n * n, nz)] = rng.standard_normal(nz)
    return a


def phase_summa(mesh, *, n: int = 8192, density: float = 0.01,
                seed: int = 0) -> list:
    """SUMMA on the (data, model) mesh against ``spgemm_reference``, both at
    full f32 matmul precision."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.spgemm import spgemm_reference, spgemm_summa

    rng = np.random.default_rng(seed)
    sharding = NamedSharding(mesh, P("data", "model"))
    a = jax.device_put(sparse_dense_matrix(rng, n, density), sharding)
    b = jax.device_put(sparse_dense_matrix(rng, n, density), sharding)
    with jax.default_matmul_precision("highest"):
        c, wall, comp, _ = run_compiled(
            functools.partial(spgemm_summa, mesh=mesh), a, b)
        ref = jax.block_until_ready(jax.jit(spgemm_reference)(a, b))
    err = float(np.abs(np.asarray(c) - np.asarray(ref)).max())
    check(np.allclose(np.asarray(c), np.asarray(ref), rtol=1e-4, atol=1e-4),
          f"SUMMA: C differs from spgemm_reference (max |err| {err})")
    return [report("SUMMA", mesh="x".join(map(str, mesh.devices.shape)),
                   n=n, density=density, max_abs_err=err,
                   **_timing(wall, comp))]


def phase_train(mesh, *, arch: str = "smollm-135m", cfg=None,
                layers: int = 2, seq: int = 256, batch: int = 8) -> list:
    """One compressed data-parallel step at k-fraction 1.0 (lossless sparse
    allreduce) against one dense step from the same state: equal loss and
    parameters, as ``test_compressed_training_matches_dense_at_full_k``.
    ``cfg`` defaults to ``arch`` at full width, cut to ``layers``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.data import make_batch
    from repro.models import build_model
    from repro.models.common import ShapeConfig
    from repro.optim import adamw_init
    from repro.train import (TrainHParams, init_ef_state,
                             make_compressed_train_step, make_train_step)

    if cfg is None:
        cfg = dataclasses.replace(get_config(arch), n_layers=layers,
                                  compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    hp = TrainHParams(ce_chunk=min(128, seq), attn_chunk=min(128, seq),
                      remat=False,
                      total_steps=100, warmup=0)
    workers = mesh.devices.size
    data = make_batch(cfg, ShapeConfig("smoke", "train", seq, batch), 0)
    data = jax.tree.map(lambda x: jax.device_put(x, NamedSharding(
        mesh, P(*(("data",) + (None,) * (x.ndim - 1))))), data)
    dense = make_train_step(model, hp)
    comp = make_compressed_train_step(model, mesh, hp, k_fraction=1.0,
                                      selector="global")
    (pd, _, md), wall_d, comp_d, _ = run_compiled(dense, params, opt, data)
    (pc, _, _, mc), wall_c, comp_c, _ = run_compiled(
        comp, params, opt, init_ef_state(params, workers), data)
    loss_d, loss_c = float(md["loss"]), float(mc["loss"])
    check(abs(loss_d - loss_c) < 1e-4,
          f"train: compressed loss {loss_c} != dense {loss_d}")
    for a, b in zip(jax.tree.leaves(pd), jax.tree.leaves(pc)):
        check(np.allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                          atol=2e-5),
              "train: compressed parameters differ from dense")
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    return [report("train/dense", arch=cfg.arch_id, layers=cfg.n_layers,
                   d_model=cfg.d_model, vocab=cfg.vocab, params=n_params,
                   loss=loss_d, **_timing(wall_d, comp_d)),
            report("train/compressed", k_fraction=1.0, loss=loss_c,
                   **_timing(wall_c, comp_c))]


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _count_cache_events() -> dict:
    import jax
    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listen(event, **_):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listen)
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the SUMMA and compressed-training "
                         "phases, on four chips")
    args = ap.parse_args(argv)

    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(REPO, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    cache = _count_cache_events()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax sees {devices[0].platform})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"jax sees {len(devices)}", file=sys.stderr)
        return 2

    print(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{cache_dir}", flush=True)
    try:
        if args.chips == 4:
            from repro.compat import make_mesh
            phase_summa(make_mesh((2, 2), ("data", "model"),
                                  devices=devices[:4]))
            phase_train(make_mesh((4,), ("data",), devices=devices[:4]))
        else:
            from repro.core import engine as E
            phase_engine("H", m=16384, n=16384, k=32, nnz=16384,
                         expect="hash")
            phase_engine("V", m=8192, n=8192, k=64, nnz=65536, expect="vec",
                         forced=tuple(r for r in E._CANONICAL
                                      if r != "sorted"),
                         batch=4)
            phase_engine("D", m=64, n=16, k=64, nnz=256, expect="spa",
                         forced=tuple(r for r in E._CANONICAL
                                      if r != "sorted"),
                         batch=4)
            phase_onehot()
            phase_stream()
    except Exception as e:  # any failed phase fails the smoke
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(f"compile cache: hits={cache['hits']} misses={cache['misses']}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
