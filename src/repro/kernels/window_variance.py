"""Per-window variance normalization — Pallas TPU kernel.

Replaces the reference code's per-window ``int_sqrt`` (11–13 % of the
paper's profile, Fig. 13).  For a stride-1 grid of 24x24 windows, the
window sums of the centred image and its square are four constant-shift
slices of each SAT (same trick as the Haar kernel, with *static* offsets
0 and 24 — no scalar prefetch needed; the 24-lane shift is a register
rotate of an aligned slab, as in the Haar kernel), followed by an
element-wise ``rsqrt`` on the VPU.  Output is 1/sigma with sigma clamped to >= 1
(paper Eq. 5 plus the reference implementation's flat-window guard).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.cascade import WINDOW

from .autotune import DEFAULT_TILE
from .haar_stage import col_shift, row_band, sat_pad_shape

_N = float(WINDOW * WINDOW)


def _inv_sigma_kernel(ii2_ref, iic_ref, o_ref, *, tile):
    ty, tx = tile
    y0 = pl.program_id(0) * ty
    x0 = pl.program_id(1) * tx

    def window_sum(ref):
        top = row_band(ref, y0, x0, 0, tile)
        bot = row_band(ref, y0, x0, WINDOW, tile)
        a = top[:, :tx]
        b = col_shift(top, WINDOW, tx)
        c = bot[:, :tx]
        d = col_shift(bot, WINDOW, tx)
        return ((d - b) - (c - a)).astype(jnp.float32)   # int32 corners

    s2 = window_sum(ii2_ref)
    s1 = window_sum(iic_ref)
    var = s2 / _N - (s1 / _N) ** 2
    o_ref[...] = jax.lax.rsqrt(jnp.maximum(var, 1.0))


def window_inv_sigma_kernel(ii2_padded: jax.Array, iic_padded: jax.Array,
                            ny: int, nx: int, *, tile=DEFAULT_TILE,
                            interpret: bool) -> jax.Array:
    """(ny, nx) inv-sigma grid; ny/nx must be tile-aligned and the SATs
    padded to :func:`~repro.kernels.haar_stage.sat_pad_shape` (wrapper
    pads)."""
    ty, tx = tile
    assert ny % ty == 0 and nx % tx == 0
    need_h, need_w = sat_pad_shape(ny, nx)
    assert ii2_padded.shape[0] >= need_h and ii2_padded.shape[1] >= need_w

    kernel = functools.partial(_inv_sigma_kernel, tile=tile)
    return pl.pallas_call(
        kernel,
        grid=(ny // ty, nx // tx),
        in_specs=[
            pl.BlockSpec(ii2_padded.shape, lambda i, j: (0, 0)),
            pl.BlockSpec(iic_padded.shape, lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((ty, tx), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((ny, nx), jnp.float32),
        interpret=interpret,
    )(ii2_padded, iic_padded)
