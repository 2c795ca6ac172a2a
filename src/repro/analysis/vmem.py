"""SPKJ204: static VMEM-budget estimator for partitioned launches.

The estimator re-uses the runtime's own working-set formula
(:func:`repro.kernels.ops.fold_working_set_bytes`) on the geometry the
runtime's own chooser would pick (:func:`partitioned_launch_geometry` +
``engine._partition_fold``), then compares against a per-backend hard cap
— so the static proof and the runtime budget cannot drift apart. The cap
is the ``vmem_limit_bytes`` every engine kernel passes to Mosaic
(``repro.kernels.VMEM_LIMIT_BYTES``), not the requested soft budget: the
lane-multiple floors in the geometry chooser are sanctioned excess over a
sub-minimal *budget*, but nothing may exceed the *cap*. On a v5e core the
cap sits inside the 128 MiB of physical VMEM; 16 MiB is only Mosaic's
default scoped limit, which applies to kernels that pass no limit.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from repro.analysis.findings import Finding
from repro.kernels import VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES

#: Per-kernel fast-memory caps (bytes). "interpret" models the TPU cap so
#: interpret-mode CI proves the geometry that will ship to hardware.
BACKEND_VMEM_CAPS: Dict[str, int] = {
    "tpu": VMEM_LIMIT_BYTES,
    "interpret": VMEM_LIMIT_BYTES,
}

DEFAULT_BACKEND = "interpret"


def working_set_bytes(fold: str, *, part_elems: int, chunk: int) -> int:
    """Working set of one grid step at a given fold/geometry — delegates to
    the runtime's single formula."""
    from repro.kernels.ops import fold_working_set_bytes
    return fold_working_set_bytes(fold, tile_elems=part_elems, chunk=chunk)


def check_launch(*, cap: int, m: int, n: int,
                 vmem_budget_bytes: int = VMEM_BUDGET_BYTES,
                 part_elems: Optional[int] = None,
                 chunk: Optional[int] = None,
                 regime: str = "vec",
                 backend: str = DEFAULT_BACKEND,
                 cost_model: Optional[Dict[str, float]] = None,
                 label: str = "") -> List[Finding]:
    """Prove one launch geometry fits the backend cap.

    With no explicit ``part_elems``/``chunk`` this checks the geometry the
    engine would actually launch for a ``cap``-long stream on an (m, n)
    accumulator; explicit overrides let tests (and the CLI) probe
    deliberately overspilled geometries.
    """
    from repro.core.engine import _partition_fold
    from repro.kernels.ops import partitioned_launch_geometry

    geom = partitioned_launch_geometry(
        cap, m=m, n=n, part_elems=part_elems,
        vmem_budget_bytes=vmem_budget_bytes, chunk=chunk)
    fold = _partition_fold(regime, geom, vmem_budget_bytes, cost_model)
    ws = working_set_bytes(fold, part_elems=geom.part_elems, chunk=geom.chunk)
    cap_bytes = BACKEND_VMEM_CAPS[backend]
    where = label or f"cap={cap},m={m},n={n},regime={regime}"
    if ws > cap_bytes:
        return [Finding(
            "SPKJ204", f"<vmem:{where}>", 0,
            f"launch working set {ws} B (fold={fold!r}, "
            f"part_elems={geom.part_elems}, chunk={geom.chunk}) exceeds the "
            f"{backend} VMEM cap {cap_bytes} B",
            "shrink part_elems/chunk (or lower vmem_budget_bytes so "
            "partitioned_launch_geometry re-tiles) until "
            "fold_working_set_bytes fits the cap")]
    return []


#: (cap, m, n, budget) sweep proved on every run: the engine defaults, a
#: tight budget, and both partitioned regimes over each.
DEFAULT_MATRIX = [
    {"cap": 4096, "m": 64, "n": 8},
    {"cap": 4096, "m": 64, "n": 8, "vmem_budget_bytes": 1 << 16},
    {"cap": 1 << 16, "m": 1024, "n": 512},
    {"cap": 1 << 16, "m": 1024, "n": 512, "vmem_budget_bytes": 1 << 20},
]


def check_all(backend: str = DEFAULT_BACKEND) -> List[Finding]:
    findings: List[Finding] = []
    for spec in DEFAULT_MATRIX:
        for regime in ("vec", "blocked_spa"):
            findings.extend(check_launch(regime=regime, backend=backend,
                                         **spec))
    return findings
