#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process is started on.

    python3 chipbench/run.py --workload er.vec --seed 7 --seconds 35 --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its
configuration file names a driver (``drivers/<driver>.py``) and a generator
(``gen/<family>.py``); its traffic file (``traffic/<traffic>.json``) holds
the sizes. Set-up makes the inputs on the device from the seed and warms
the generator and the system under test with one call each; ``setup_s``
runs from process start to the end of that warm-up. The window is a closed
loop with one caller: each step calls the system on a fresh input made
from ``(seed, step)`` and waits for its result.
``--trace 1`` traces a short window instead and prints the per-layer
metrics read from the device trace.

After the window a sample of the results, drawn from the seed, is compared
with a plain reference (``reference.py``); each number compared is printed
beside its limit on stderr, and ``correct`` says whether all are within.
The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``breakdown`` when traced, and
``check`` last. With no TPU, or fewer chips than the cell asks for, the
run exits with code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import trace_reduce  # noqa: E402


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def process_age_s() -> float:
    """Seconds since this process started, from ``/proc/self/stat``."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    ticks = int(fields[19])  # field 22, starttime, in clock ticks
    return (time.clock_gettime(time.CLOCK_BOOTTIME)
            - ticks / os.sysconf("SC_CLK_TCK"))


def _load_json(rel: str):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Spec:
    """One cell as ``BENCHMARK.json`` and its files describe it."""
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def cell_spec(workload: str) -> Spec:
    bench = _load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return Spec(cell, _load_json(entry["file"]),
                _load_json(f"chipbench/traffic/{cell['traffic']}.json"),
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


@dataclasses.dataclass
class Window:
    """What the end-to-end and per-layer readers take."""
    setup_s: float
    window_s: float
    call_s: list
    work: float
    steps: int
    counts: dict
    modules: dict
    peaks: dict | None = None


class Reservoir:
    """A uniform sample of ``size`` steps of the window, drawn from the seed
    (algorithm R), holding each sampled step's result."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = np.random.default_rng([seed, 0x5A3])
        self.items: list = []
        self.seen = 0

    def offer(self, step: int, result) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append((step, result))
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.size:
            self.items[j] = (step, result)


def _configure_jax():
    import jax
    cache = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def _devices(jax, chips: int, require_accelerator: bool):
    devices = jax.devices()
    if require_accelerator:
        if devices[0].platform != "tpu":
            raise NoAccelerator(f"no TPU: jax sees {devices[0].platform}")
        if len(devices) < chips:
            raise NoAccelerator(f"the cell needs {chips} chips, jax sees "
                                f"{len(devices)}")
    return devices


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def _loop(cell, seconds: float, min_steps: int, reservoir: Reservoir,
          counted: bool):
    """The closed loop: returns (window seconds, per-call seconds, steps,
    per-step counts).

    The input of the next step is generated while the current call runs:
    the device runs it after the call, so the call's time is its own, and
    the host's work to launch the generator stays out of the window's
    idle time."""
    import jax
    from jax.profiler import TraceAnnotation

    call_s, counts = [], {}
    start = time.perf_counter()
    with TraceAnnotation("gen"):
        x = cell.inputs(0)
    step = 0
    while True:
        t0 = time.perf_counter()
        with TraceAnnotation("engine_call"):
            y = cell.call(x)
        with TraceAnnotation("gen"):
            x = cell.inputs(step + 1)
        with TraceAnnotation("block"):
            y = jax.block_until_ready(y)
        t1 = time.perf_counter()
        call_s.append(t1 - t0)
        if counted:
            for k, v in cell.counts(y).items():
                counts.setdefault(k, []).append(v)
        reservoir.offer(step, y)
        del y
        step += 1
        if t1 - start >= seconds and step >= min_steps:
            return t1 - start, call_s, step, counts


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        system=None, traffic: dict | None = None,
        require_accelerator: bool = True, log=sys.stderr) -> dict:
    """Run one cell and return its result line as a dict.

    ``system`` replaces the program's entry point (the control and the
    fault tests put theirs in its place); ``traffic`` overrides entries of
    the traffic file (tests run toy sizes).
    """
    spec = cell_spec(workload)
    tr = dict(spec.traffic, **(traffic or {}))
    jax = _configure_jax()
    chips = spec.workload["chips"]
    devices = _devices(jax, chips, require_accelerator)
    kind = devices[0].device_kind
    peaks = trace_reduce.load_peaks(kind) if require_accelerator else None

    driver = load_module("drivers", spec.config["driver"])
    gen = load_module("gen", spec.config["family"])
    cell = driver.Cell(config=spec.config, traffic=tr, seed=seed,
                       devices=devices[:chips], gen=gen, system=system)
    cell.warm()
    setup_s = process_age_s()

    reservoir = Reservoir(int(tr["check_samples"]), seed)
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(trace_dir)
        budget = min(seconds, float(tr["trace_seconds"]))
    else:
        budget = seconds
    try:
        window_s, call_s, steps, counts = _loop(
            cell, budget, int(tr["min_steps"]), reservoir, counted=trace)
    finally:
        if trace:
            jax.profiler.stop_trace()
    memory_peak = _memory_peak(devices[:chips])
    print(f"window {window_s!r} s: {steps} calls taking {sum(call_s)!r} s, "
          f"median {sorted(call_s)[len(call_s) // 2]!r} s, slowest "
          f"{max(call_s)!r} s", file=log)

    win = Window(setup_s, window_s, call_s, steps * cell.work_per_call,
                 steps, counts, cell.modules, peaks)
    metrics: dict = {}
    breakdown = None
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if trace:
        try:
            tr_sum = trace_reduce.read_xspace(
                trace_reduce.find_xspace(trace_dir), [f"/device:{d.platform.upper()}:{d.id}"
                         for d in devices[:chips]])
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr_sum.busy_s()
        device["window_s"] = tr_sum.window_s()
        for m in spec.per_layer:
            value = load_module("layers", m["name"]).read(tr_sum, win)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = tr_sum.breakdown()
    else:
        for m in spec.end_to_end:
            metrics[m["name"]] = {"value": load_module("e2e", m["name"])
                                  .read(win), "unit": m["unit"]}

    # the comparison: results to the host, the program's buffers freed,
    # then the reference over the sampled steps
    sampled = [(s, cell.fetch(y)) for s, y in reservoir.items]
    reservoir.items.clear()
    limits = spec.config["limits"]
    worst: dict = {}
    failed = 0
    for s, got in sampled:
        numbers = cell.check(s, got)
        failed += any(not numbers[n] <= limits[n] for n in limits)
        for n in limits:
            v = numbers[n]
            if n not in worst or math.isnan(v) or v > worst[n]:
                worst[n] = v
    check = {n: {"value": worst.get(n), "limit": limits[n]} for n in limits}
    correct = bool(sampled) and failed == 0
    for n, c in check.items():
        print(f"check {n} {c['value']!r} limit {c['limit']!r} "
              f"({len(sampled)} sampled steps)", file=log)
    out = {"correct": correct, "attempted": steps, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except (NoAccelerator, trace_reduce.UnknownDevice) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
