"""Pallas kernel: block-local top-k selection for gradient sparsification.

The top-k selector is the hot non-matmul op of the paper's DL use case
(compress every gradient tensor every step). Global ``lax.top_k`` over 10⁸
elements sorts far more than needed; production systems select top-(k/nb)
within fixed blocks (SparCML-style). This kernel does one block per grid
cell: the block lives in VMEM, selection runs as k rounds of
max+mask (k ≪ block, so O(k·block) beats a full sort), and indices are
emitted globally offset. ref.topk_block_ref is the oracle. Interpreter-only:
a CPU reference that refuses to launch on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.compat import pallas as pl
from repro.compat import require_interpreter


def _topk_kernel(x_ref, idx_ref, val_ref, *, block: int, k: int):
    b = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    mag = jnp.abs(x)
    base = b * block

    def body(i, carry):
        mag_cur, _ = carry
        j = jnp.argmax(mag_cur)
        idx_ref[i] = (base + j).astype(jnp.int32)
        val_ref[i] = x[j]
        mag_next = mag_cur.at[j].set(-1.0)
        return mag_next, 0

    jax.lax.fori_loop(0, k, body, (mag, 0))


def topk_block_raw(x: jax.Array, *, k: int, block: int):
    """x: (nb*block,) -> (idx (nb*k,), val (nb*k,)); top-k by |value| per
    block."""
    require_interpreter("topk_block_raw")
    if x.shape[0] % block != 0:
        raise ValueError(f"input length {x.shape[0]} must be a multiple of "
                         f"block {block}")
    nb = x.shape[0] // block
    kernel = functools.partial(_topk_kernel, block=block, k=k)
    idx, val = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block,), lambda b: (b,))],
        out_specs=[pl.BlockSpec((k,), lambda b: (b,)),
                   pl.BlockSpec((k,), lambda b: (b,))],
        out_shape=[jax.ShapeDtypeStruct((nb * k,), jnp.int32),
                   jax.ShapeDtypeStruct((nb * k,), jnp.float32)],
        interpret=True,
    )(x)
    return idx, val
