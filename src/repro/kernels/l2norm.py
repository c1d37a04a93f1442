"""Pallas TPU kernel: fused Σw² grid reduction for the AWP monitor.

The paper's profile (Tables II/III) shows the AWP l²-norm as the algorithm's
only measurable cost, so it gets a fused kernel: one pass over the weights,
accumulating one ``(8, 128)`` vreg-shaped partial sum across sequential grid
steps (output block revisited every step; initialised on step 0). The TPU
compiler stores no scalars to VMEM, so the final 1024-way sum runs outside
the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitpack import LANES, resolve_interpret

NORM_BLOCK_ROWS = 512
SUBLANES = 8


def _l2norm_kernel(w_ref, acc_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    x = w_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.sum((x * x).reshape(-1, SUBLANES, LANES), axis=0)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def l2norm_sq_2d(
    w: jnp.ndarray,
    *,
    interpret: bool | None = None,
    block_rows: int = NORM_BLOCK_ROWS,
) -> jnp.ndarray:
    """Σw² of a ``(rows, 128)`` fp32 array -> f32 scalar."""
    rows, lanes = w.shape
    if lanes != LANES:
        raise ValueError(f"last dim must be {LANES}, got {lanes}")
    if rows % block_rows:
        raise ValueError(f"rows ({rows}) must be a multiple of {block_rows}")
    grid = (rows // block_rows,)
    interpret = resolve_interpret(interpret)
    out = pl.pallas_call(
        _l2norm_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUBLANES, LANES), jnp.float32),
        interpret=interpret,
    )(w)
    return jnp.sum(out)
