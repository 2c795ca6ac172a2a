"""The one home for the jax API surface that moves between releases.

The repo targets jax 0.9.0 only (``requirements.txt``). This module is the
**one sanctioned home for ``jax.experimental`` imports** (spkaddlint rule
SPK102): experimental APIs move between jax releases, so every consumer
routes through the re-exports below (``pallas`` / ``pallas_tpu`` /
``topologies``) and the next upgrade stays a one-file problem. It also
holds the two process-wide decisions that depend on the installed jax:
how a mesh is built (:func:`make_mesh`) and whether Pallas kernels run
through Mosaic or the interpreter (:func:`interpret_kernels`).
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pallas
from jax.experimental import topologies as topologies
from jax.experimental.pallas import tpu as pallas_tpu
from jax.sharding import AxisType

shard_map = jax.shard_map


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    jax 0.9 defaults to Explicit axes, under which ``with_sharding_constraint``
    outside ``jax.set_mesh`` and specs that name one axis twice are refused.
    The repo's sharding rules are written for Auto axes, so every mesh is
    built here.
    """
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def interpret_kernels() -> bool:
    """Whether the engine's Pallas kernels run under the interpreter.

    Decided once, from the backend: on a TPU every engine ``pallas_call``
    lowers through Mosaic; on any other backend (the CPU test lanes) it
    runs under the Pallas interpreter.
    """
    return jax.default_backend() != "tpu"


def require_interpreter(kernel: str) -> None:
    """Refuse to launch an interpreter-only reference kernel on a TPU.

    The faithful hash, legacy sliding-SPA and block top-k kernels are CPU
    references that the engine never reaches; they are not written for
    Mosaic, so on a TPU they raise instead of quietly interpreting.
    """
    if not interpret_kernels():
        raise NotImplementedError(
            f"{kernel} is an interpreter-only CPU reference kernel; the "
            f"engine regimes (spkadd_run / spkadd_auto) are the TPU paths")


def backend_initialized() -> bool:
    """True iff jax has already initialized an XLA backend in this process —
    the point at which ``XLA_FLAGS`` is read and the device count locks.

    Reads the private backend cache (``jax._src.xla_bridge._backends``); if
    the internal layout ever drifts, this *fails open* (returns False) —
    callers that need certainty about the device count must check
    ``jax.device_count()`` after init.
    """
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:
        return False


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (``{}`` when the backend
    reports nothing)."""
    return dict(compiled.cost_analysis() or {})
