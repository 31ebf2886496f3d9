"""Packed-window cascade stage evaluation — Pallas kernel (sparse tail).

The dense tile kernel (:mod:`repro.kernels.haar_stage`) exploits the fact
that on a stride-1 grid every weak classifier's SAT corner is the same 2-D
slice shifted by a constant.  The *packed tail* breaks that structure: after
survivor compaction the window list is a flat vector whose entries live on
different images and pyramid levels, addressed through per-window (SAT
offset, row stride) pairs — the gather-based evaluators in
:mod:`repro.kernels.packed_tail` are the natural XLA expression of it.

This kernel is the *blocked* expression of the same computation, for the
high-density regime where the packed list is large (many survivors / many
changed windows): lanes are processed in ``tile``-shaped blocks
(8 x 128 window origins, one per VPU lane), the flattened multi-level SAT
is resident once per dispatch, and a whole *run of stages* ``[s0, s1)`` is
evaluated per block — one dispatch replaces ``s1 - s0`` per-stage gather
dispatches, and each block's corner lookups touch a bounded working set
instead of streaming the full ``(K, 3, cap)`` index space per stage.  The
kernel-vs-gather crossover is measured, not assumed: see
``packed_tail.measure_rungs`` and the density sweep in ``bench_detector``.

Weak-classifier geometry / thresholds / votes are scalar-prefetched (same
``PrefetchScalarGridSpec`` layout as the dense kernel) and read wholesale,
so the corner addressing is vectorized over all ``K`` weak classifiers of
the run: 4 bulk index-loads per rectangle corner, exactly the bulk-gather
backend's access pattern but per lane-block.  Arithmetic matches the
gather oracle bit-for-bit: same corner combination order
``(d - b - c + a)``, same ``feat * inv_sigma / AREA`` normalization, weak
votes summed in ascending-``k`` order within each stage.

Runs in the Pallas interpreter only.  Mosaic refuses it for TPU: the
wholesale reads of the scalar-prefetch refs load vectors from SMEM, and
``jnp.take`` over the flat SAT is a table lookup, while Mosaic's gather
only permutes within a 2-D vector.  So on TPU a ``"pallas"`` tail raises
:data:`MOSAIC_REFUSAL` instead of running (it is never swapped for
another backend), and the tail tuners leave this backend out there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cascade import WINDOW

from .autotune import DEFAULT_TILE

_AREA = float(WINDOW * WINDOW)

# what Mosaic (jax 0.9.0, libtpu 0.0.34) says when compiling this kernel
# for TPU v5e, first for the SMEM reads and then, with those unrolled, for
# the flat-SAT lookups; tests/test_tpu_compile.py checks it still does
MOSAIC_REFUSAL = (
    "the packed-window Pallas kernel does not compile for TPU: Mosaic "
    "refuses its whole-array scalar-prefetch reads ('Can only load scalars "
    "from SMEM') and its flat-SAT lookups ('Only 2D gather is "
    "supported'); use tail_backend 'bulk' or 'gather', or tail_rungs "
    "measured on this backend")


def _packed_kernel(rx_ref, rw_ref, th_ref, lv_ref, rv_ref,  # SMEM (prefetch)
                   sat_ref, off_ref, st_ref, y_ref, x_ref, inv_ref,
                   o_ref, *, rel_bounds, tile):
    """One lane-block of packed windows through stages [s0, s1).

    ``rel_bounds`` are the run's weak-classifier boundaries relative to the
    run start (static), so stage ``si`` owns votes
    ``[rel_bounds[si], rel_bounds[si+1])``.
    """
    sat = sat_ref[...]                      # (1, B*S) flat multi-level SATs
    off = off_ref[...]                      # (ty, tx) absolute SAT offsets
    st = st_ref[...]                        # (ty, tx) per-window row strides
    yy = y_ref[...]
    xx = x_ref[...]
    inv = inv_ref[...]
    rects = rx_ref[...]                     # (K, 3, 4) int32 [x, y, w, h]
    w = rw_ref[...]                         # (K, 3)

    # vectorized over every weak classifier of the run: corner index grids
    # are (K, 3, ty, tx); one bulk index-load per rect corner
    x0 = xx[None, None] + rects[:, :, 0][:, :, None, None]
    y0 = yy[None, None] + rects[:, :, 1][:, :, None, None]
    x1 = x0 + rects[:, :, 2][:, :, None, None]
    y1 = y0 + rects[:, :, 3][:, :, None, None]

    def g(y, x):
        return jnp.take(sat, off[None, None] + y * st[None, None] + x,
                        mode="clip")

    area = (g(y1, x1) - g(y0, x1) - g(y1, x0) + g(y0, x0)   # (K, 3, ty, tx)
            ).astype(jnp.float32)                   # int32 corners, exact
    feat = jnp.zeros((rects.shape[0],) + tile, jnp.float32)
    for r in range(3):                      # static unroll: <= 3 rects
        feat = feat + w[:, r, None, None] * area[:, r]
    f_norm = feat * inv[None] / _AREA
    votes = jnp.where(f_norm < th_ref[...][:, None, None],
                      lv_ref[...][:, None, None], rv_ref[...][:, None, None])
    for si in range(len(rel_bounds) - 1):   # one output plane per stage
        acc = jnp.zeros(tile, jnp.float32)
        for k in range(rel_bounds[si], rel_bounds[si + 1]):
            acc = acc + votes[k]            # ascending-k, like the oracle
        o_ref[si] = acc


def packed_stage_sums_kernel(rect_xywh: jax.Array, rect_w: jax.Array,
                             wc_threshold: jax.Array, left_val: jax.Array,
                             right_val: jax.Array, rel_bounds: tuple,
                             sat_flat: jax.Array, off: jax.Array,
                             stride: jax.Array, ys: jax.Array, xs: jax.Array,
                             inv_sigma: jax.Array, *, tile=DEFAULT_TILE,
                             interpret: bool) -> jax.Array:
    """Stage-run vote sums over a blocked packed window list.

    sat_flat: (1, N) int32 every image's every level's SAT,
      flattened+concatenated.
    off/stride/ys/xs: (n_rows, tx) int32 per-window addressing, tile-aligned
      (``n_rows`` a multiple of ``tile[0]``; the ops wrapper pads).
    inv_sigma: (n_rows, tx) float32 normalization.
    Returns (n_stages_run, n_rows, tx) float32 stage sums.  Raises
    ``NotImplementedError(MOSAIC_REFUSAL)`` when asked to compile
    (``interpret=False``).
    """
    if not interpret:
        raise NotImplementedError(MOSAIC_REFUSAL)
    return _packed_call(rect_xywh, rect_w, wc_threshold, left_val,
                        right_val, rel_bounds, sat_flat, off, stride, ys, xs,
                        inv_sigma, tile=tile, interpret=interpret)


def _packed_call(rect_xywh, rect_w, wc_threshold, left_val, right_val,
                 rel_bounds, sat_flat, off, stride, ys, xs, inv_sigma, *,
                 tile, interpret: bool) -> jax.Array:
    n_rows, tx = off.shape
    ty = tile[0]
    assert tx == tile[1] and n_rows % ty == 0, (off.shape, tile)
    n_run = len(rel_bounds) - 1

    kernel = functools.partial(_packed_kernel, rel_bounds=rel_bounds,
                               tile=tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_rows // ty,),
        in_specs=[
            # full flat SAT resident (index map constant → loaded once)
            pl.BlockSpec(sat_flat.shape, lambda i, *_: (0, 0)),
            pl.BlockSpec((ty, tile[1]), lambda i, *_: (i, 0)),
            pl.BlockSpec((ty, tile[1]), lambda i, *_: (i, 0)),
            pl.BlockSpec((ty, tile[1]), lambda i, *_: (i, 0)),
            pl.BlockSpec((ty, tile[1]), lambda i, *_: (i, 0)),
            pl.BlockSpec((ty, tile[1]), lambda i, *_: (i, 0)),
        ],
        out_specs=pl.BlockSpec((n_run, ty, tile[1]), lambda i, *_: (0, i, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_run, n_rows, tx), jnp.float32),
        interpret=interpret,
    )(rect_xywh.astype(jnp.int32), rect_w.astype(jnp.float32),
      wc_threshold.astype(jnp.float32), left_val.astype(jnp.float32),
      right_val.astype(jnp.float32), sat_flat,
      off.astype(jnp.int32), stride.astype(jnp.int32),
      ys.astype(jnp.int32), xs.astype(jnp.int32),
      inv_sigma.astype(jnp.float32))
