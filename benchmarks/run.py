"""Benchmark harness front door: one module per paper table/figure.

``python -m benchmarks.run [--only NAME] [--quick]`` prints
``name,us_per_call,derived`` CSV lines (benchmarks/common.emit).

Paper artifact -> module:
  Table III (ER runtimes)        table34_algorithms.run('er')
  Table IV  (RMAT runtimes)      table34_algorithms.run('rmat')
  Fig. 2    (best-algo regions)  fig2_regions
  Fig. 3    (scaling)            fig3_scaling (work-scaling exponents)
  Fig. 4    (hash-table size)    fig4_blocksize (VMEM tile sweep)
  Fig. 6    (SpGEMM impact)      fig6_spgemm (4-device sparse SUMMA)
  §I DL use-case                 sparse_allreduce_bytes (8-device DP)
"""
from __future__ import annotations

import argparse
import importlib
import sys
import traceback


#: (job name, module, entry) in run order. The two multi-device benches
#: run in child processes and come first: once this process initialises a
#: jax backend it may hold the chip, and a child that needs it would fail or
#: hang. Modules are imported per job, so nothing touches jax before then.
JOBS = [
    ("fig6_spgemm", "fig6_spgemm", "main"),
    ("sparse_allreduce", "sparse_allreduce_bytes", "main"),
    ("table3_er", "table34_algorithms", "run:er"),
    ("table4_rmat", "table34_algorithms", "run:rmat"),
    ("fig2_regions", "fig2_regions", "main"),
    ("fig3_scaling", "fig3_scaling", "main"),
    ("fig4_blocksize", "fig4_blocksize", "main"),
    ("kv_quant_roofline", "kv_quant_roofline", "main"),
]
MULTIDEVICE = {"fig6_spgemm", "sparse_allreduce"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip-multidevice", action="store_true",
                    help="skip benches that spawn multi-device subprocesses")
    args = ap.parse_args()

    failures = []
    for name, module, entry in JOBS:
        if args.only and args.only != name:
            continue
        if args.skip_multidevice and name in MULTIDEVICE:
            continue
        print(f"# --- {name} ---", flush=True)
        try:
            mod = importlib.import_module(f"benchmarks.{module}")
            fn_name, _, arg = entry.partition(":")
            fn = getattr(mod, fn_name)
            fn(arg) if arg else fn()
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        sys.exit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
