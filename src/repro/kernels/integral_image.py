"""Tiled integral-image (summed-area table) Pallas TPU kernel.

The CPU reference computes a 2-D prefix sum with two running-sum loops; the
TPU re-expression is two *tiled scan passes* that exploit the sequential
grid-iteration order of ``pallas_call`` on TPU:

  pass 1 (rows):  grid = (H/TH, W/TW), the column index innermost.  Each
     step computes the intra-tile row cumsum on the VPU and adds a carry
     vector (TH, 1) held in VMEM scratch that accumulates the full row sums
     of all tiles to the left.  The carry is reset when a new tile-row
     starts.
  pass 2 (cols):  symmetric, with the row index innermost and a (1, TW)
     carry.

Tile shape (8, 128)xf32 = the native VPU tile — every cumsum and the carry
broadcast are lane-aligned.  Grid-order carry accumulation is the idiomatic
TPU replacement for the sequential dependence of a prefix sum; HBM traffic
is 2 reads + 2 writes of the image (the roofline floor for a 2-pass SAT).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import DEFAULT_TILE


def _row_scan_kernel(x_ref, o_ref, carry_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[...]
    c = carry_ref[...]                       # (TH, 1)
    o_ref[...] = jnp.cumsum(x, axis=1) + c
    carry_ref[...] = c + jnp.sum(x, axis=1, keepdims=True)


def _col_scan_kernel(x_ref, o_ref, carry_ref):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[...]
    c = carry_ref[...]                       # (1, TW)
    o_ref[...] = jnp.cumsum(x, axis=0) + c
    carry_ref[...] = c + jnp.sum(x, axis=0, keepdims=True)


def integral_image_kernel(img: jax.Array, *, tile=DEFAULT_TILE,
                          interpret: bool) -> jax.Array:
    """Inclusive 2-D cumsum of ``img`` (H, W) → float32 (H, W).

    H and W must be multiples of the tile (the ops.py wrapper pads).
    """
    h, w = img.shape
    th, tw = tile
    assert h % th == 0 and w % tw == 0, (h, w, tile)
    img = img.astype(jnp.float32)

    row = pl.pallas_call(
        _row_scan_kernel,
        grid=(h // th, w // tw),             # col index innermost/sequential
        in_specs=[pl.BlockSpec((th, tw), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((th, tw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((h, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((th, 1), jnp.float32)],
        interpret=interpret,
    )(img)

    col = pl.pallas_call(
        _col_scan_kernel,
        grid=(w // tw, h // th),             # row index innermost/sequential
        in_specs=[pl.BlockSpec((th, tw), lambda j, i: (i, j))],
        out_specs=pl.BlockSpec((th, tw), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((h, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, tw), jnp.float32)],
        interpret=interpret,
    )(row)
    return col
