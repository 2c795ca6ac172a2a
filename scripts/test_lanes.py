#!/usr/bin/env python
"""Deterministic file-level lane assignment for CI's tier-1 matrix split.

Usage:
    python scripts/test_lanes.py N_LANES LANE_INDEX   # prints lane's files
    python scripts/test_lanes.py N_LANES --all        # prints every lane

Every ``tests/test_*.py`` is assigned to exactly one lane by greedy
bin-packing on measured-duration weights (heaviest file first onto the
currently lightest lane), so:

- new test files are covered automatically (default weight 1) — a file can
  never silently drop out of CI;
- the assignment is a pure function of the file list, so all matrix jobs
  agree without coordination;
- each lane keeps pytest's ``-x`` fail-fast semantics internally.

Weights are coarse relative costs from ``pytest --durations`` on the CI
image (test_system's end-to-end launcher runs dominate); update them when
the balance drifts — only the ratio matters.
"""
from __future__ import annotations

import os
import sys

# relative wall-clock weight per file (~10s units; default 1)
WEIGHTS = {
    "test_system.py": 26,
    "test_distributed.py": 15,
    "test_models_smoke.py": 8,
    "test_spkadd.py": 6,
    "test_engine.py": 5,
    "test_vec_accum.py": 5,
    "test_partition.py": 5,
    "test_kernels.py": 4,
    "test_delta_sync.py": 4,
    "test_stream_service.py": 4,
    "test_hash_accum.py": 5,
    "test_lanes.py": 1,
    "test_analysis.py": 3,
    "test_layers.py": 3,
    "test_extensions.py": 3,
    "test_sharding.py": 2,
    "test_obs.py": 2,
    "test_chip_smoke.py": 3,
}

TESTS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests")


def lanes(n_lanes: int) -> list[list[str]]:
    files = sorted(f for f in os.listdir(TESTS_DIR)
                   if f.startswith("test_") and f.endswith(".py"))
    order = sorted(files, key=lambda f: (-WEIGHTS.get(f, 1), f))
    bins: list[list[str]] = [[] for _ in range(n_lanes)]
    loads = [0] * n_lanes
    for f in order:
        i = loads.index(min(loads))  # lightest lane; ties -> lowest index
        bins[i].append(f)
        loads[i] += WEIGHTS.get(f, 1)
    return [sorted(b) for b in bins]


def main():
    if len(sys.argv) != 3:
        sys.exit(__doc__)
    n = int(sys.argv[1])
    assignment = lanes(n)
    if sys.argv[2] == "--all":
        for i, b in enumerate(assignment):
            load = sum(WEIGHTS.get(f, 1) for f in b)
            print(f"lane {i} (weight {load}): " +
                  " ".join(os.path.join("tests", f) for f in b))
        return
    idx = int(sys.argv[2])
    if not 0 <= idx < n:
        sys.exit(f"lane index {idx} out of range for {n} lanes")
    print(" ".join(os.path.join("tests", f) for f in assignment[idx]))


if __name__ == "__main__":
    main()
