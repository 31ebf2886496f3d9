"""Haar cascade stage evaluation — Pallas TPU kernel (the paper's hotspot).

``evalWeakClassifier`` + ``runCascadeClassifier`` are 83–85 % of the paper's
sequential runtime (Fig. 13).  The CPU code walks windows one by one and,
per window, gathers 4 SAT corners per rectangle.  That access pattern is
hostile to a vector unit, so the TPU kernel inverts the loop structure:

  * a *tile of window origins* (8 x 128, one per VPU lane) is evaluated
    simultaneously;
  * for a fixed weak classifier, the SAT corner of rectangle r for every
    window in the tile is the **same 2-D slice of the SAT shifted by a
    constant** — so each rectangle costs two aligned slab loads (rows
    ``y`` and ``y + h``) from the VMEM-resident SAT, four register
    rotates that bring its corners into place, and pure element-wise VPU
    arithmetic.  No gathers anywhere.  (Mosaic loads VMEM only at
    dynamic offsets aligned to the (8, 128) vreg; the corner offsets are
    arbitrary, hence slab + rotate — see :func:`row_band`.)
  * weak-classifier geometry (rect x/y/w/h), weights, thresholds and votes
    are **scalar-prefetched into SMEM** (as flat vectors, see
    :func:`weak_vote`) so the slice offsets are scalars — the TPU-legal
    way to do data-dependent addressing.

The kernel computes one stage's summed votes for every window in the tile;
the engine applies the stage threshold and handles early-exit/compaction
(see repro.core.engine).  Stride-1 window grids only (the engine routes
strided/compacted evaluation to the gather oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cascade import WINDOW

from .autotune import DEFAULT_TILE

_INV_AREA = 1.0 / float(WINDOW * WINDOW)

# f32 vreg geometry: Mosaic loads a VMEM slice only at a dynamic offset it
# can prove to be a multiple of these (sublanes on dim 0, lanes on dim 1)
SUBLANES = 8
LANES = 128


def sat_pad_shape(ny_pad: int, nx_pad: int) -> tuple[int, int]:
    """Padded SAT shape that holds every aligned slab a (ny_pad, nx_pad)
    grid of windows loads: up to ``WINDOW`` rows of corner offset plus one
    sublane of slack below, one lane vreg of slack to the right."""
    return ny_pad + WINDOW + SUBLANES, nx_pad + LANES


def row_band(ref, r0, c0, dy, tile):
    """``ref[r0+dy : r0+dy+ty, c0 : c0+tx+LANES]`` for a sublane-aligned
    ``r0``, a lane-aligned ``c0`` and a dynamic ``dy >= 0``.

    Loads the aligned slab that holds those rows and rotates them into
    place: values only move, so the float results are those of a direct
    unaligned load.
    """
    ty, tx = tile
    start = pl.multiple_of(r0 + (dy // SUBLANES) * SUBLANES, SUBLANES)
    slab = ref[pl.ds(start, ty + SUBLANES),
               pl.ds(pl.multiple_of(c0, LANES), tx + LANES)]
    n = ty + SUBLANES
    return pltpu.roll(slab, (n - dy % SUBLANES) % n, 0)[:ty]


def col_shift(band, dx, tx: int):
    """``band[:, dx : dx + tx]`` for a dynamic ``0 <= dx < LANES`` (lane
    rotate of a :func:`row_band`)."""
    n = band.shape[1]
    return pltpu.roll(band, (n - dx) % n, 1)[:, :tx]


def weak_vote(k, rx_ref, rw_ref, th_ref, lv_ref, rv_ref, ii_ref,
              inv_sigma, y0, x0, tile):
    """Vote of weak classifier ``k`` for every window origin of the tile
    at ``(y0, x0)``, in :func:`repro.core.features.eval_weak_classifier`'s
    float ordering (``d - b - c + a`` corners; XLA turns its ``/ AREA``
    into this multiply by the reciprocal).

    ``ii_ref`` is the int32 summed-area table
    (:mod:`repro.core.integral`): the four corners are differenced in
    int32, exact under wrap-around, and the rectangle sum is converted to
    float32 after.

    The weak-classifier tables are flat SMEM vectors — ``rx_ref`` holds
    (x, y, w, h) per rect, 12 entries per classifier, ``rw_ref`` 3 rect
    weights per classifier — because SMEM pads a (K, 3, 4) table to far
    more than its size: the paper cascade's 2,913 classifiers would need
    6 MB as a 3-D table, against 1 MB of SMEM.
    """
    tx = tile[1]
    feat = jnp.zeros(tile, jnp.float32)
    for r in range(3):                        # static unroll: ≤3 rects
        base = (k * 3 + r) * 4
        x, y = rx_ref[base], rx_ref[base + 1]
        w, h = rx_ref[base + 2], rx_ref[base + 3]
        top = row_band(ii_ref, y0, x0, y, tile)
        bot = row_band(ii_ref, y0, x0, y + h, tile)
        rect = (col_shift(bot, x + w, tx) - col_shift(top, x + w, tx)
                - col_shift(bot, x, tx) + col_shift(top, x, tx))
        feat = feat + rw_ref[k * 3 + r] * rect.astype(jnp.float32)
    f_norm = feat * inv_sigma * _INV_AREA
    return jnp.where(f_norm < th_ref[k], lv_ref[k], rv_ref[k])


def weak_tables(rect_xywh, rect_w, wc_threshold, left_val, right_val):
    """The five scalar-prefetch operands of :func:`weak_vote`, flat."""
    return (rect_xywh.astype(jnp.int32).reshape(-1),
            rect_w.astype(jnp.float32).reshape(-1),
            wc_threshold.astype(jnp.float32), left_val.astype(jnp.float32),
            right_val.astype(jnp.float32))


def _stage_kernel(rx_ref, rw_ref, th_ref, lv_ref, rv_ref,  # SMEM (prefetch)
                  ii_ref, inv_ref, o_ref, *, tile, n_weak):
    ty, tx = tile
    y0 = pl.program_id(0) * ty
    x0 = pl.program_id(1) * tx
    inv_sigma = inv_ref[...]

    def body(k, acc):
        return acc + weak_vote(k, rx_ref, rw_ref, th_ref, lv_ref, rv_ref,
                               ii_ref, inv_sigma, y0, x0, tile)

    o_ref[...] = jax.lax.fori_loop(0, n_weak, body,
                                   jnp.zeros(tile, jnp.float32))


def haar_stage_sums_kernel(rect_xywh: jax.Array, rect_w: jax.Array,
                           wc_threshold: jax.Array, left_val: jax.Array,
                           right_val: jax.Array, ii_padded: jax.Array,
                           inv_sigma: jax.Array, *, tile=DEFAULT_TILE,
                           interpret: bool) -> jax.Array:
    """Stage sums over a stride-1 window grid.

    ii_padded: padded int32 SAT of at least :func:`sat_pad_shape`
      ``(ny_pad, nx_pad)`` (the wrapper pads, so every slab the kernel
      loads is in-bounds).
    inv_sigma: (ny_pad, nx_pad) normalization grid, tile-aligned.
    Returns (ny_pad, nx_pad) float32 stage sums.
    """
    ny, nx = inv_sigma.shape
    ty, tx = tile
    assert ny % ty == 0 and nx % tx == 0, (ny, nx, tile)
    need_h, need_w = sat_pad_shape(ny, nx)
    assert ii_padded.shape[0] >= need_h and ii_padded.shape[1] >= need_w, (
        ii_padded.shape, (need_h, need_w))
    n_weak = int(rect_xywh.shape[0])

    kernel = functools.partial(_stage_kernel, tile=tile, n_weak=n_weak)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(ny // ty, nx // tx),
        in_specs=[
            # full SAT resident in VMEM (index map constant → loaded once)
            pl.BlockSpec(ii_padded.shape, lambda i, j, *_: (0, 0)),
            pl.BlockSpec((ty, tx), lambda i, j, *_: (i, j)),
        ],
        out_specs=pl.BlockSpec((ty, tx), lambda i, j, *_: (i, j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ny, nx), jnp.float32),
        interpret=interpret,
    )(*weak_tables(rect_xywh, rect_w, wc_threshold, left_val, right_val),
      ii_padded, inv_sigma.astype(jnp.float32))
