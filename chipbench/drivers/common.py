"""Helpers the drivers share."""
from __future__ import annotations

import jax
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: its low 32 bits seed the key and
    the rest are folded in, so seeds past 2**32 stay distinct."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def step_arg(step: int) -> np.ndarray:
    """The step index as the generators take it: a uint32 argument, so one
    compiled generator serves every step."""
    return np.uint32(step)
