"""Public jit'd wrappers over the Pallas kernels (with jnp-ref fallback).

All wrappers handle tile padding/unpadding so callers see natural shapes.
Interpret mode follows the backend (:mod:`repro.kernels.platform`): the
kernels compile through Mosaic on TPU and run in the Pallas interpreter
on CPU, where they are validated against ``ref.py``.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.cascade import Cascade
from . import ref
from .autotune import DEFAULT_TILE
from .integral_image import integral_image_kernel
from .haar_stage import haar_stage_sums_kernel, sat_pad_shape
from .window_variance import window_inv_sigma_kernel
from .packed_window import packed_stage_sums_kernel
from .fused_head import fused_head_kernel
from .platform import interpret_mode
from .tile_change import (tile_change_mask_kernel,
                          changed_window_map_kernel)

__all__ = ["integral_image", "window_inv_sigma_grid", "dense_stage_sums",
           "integral_image_batch", "window_inv_sigma_grid_batch",
           "dense_stage_sums_batch", "dense_stage_sums_batch_ref",
           "packed_stage_sums", "packed_stage_sums_ref",
           "fused_head", "fused_head_ref",
           "fused_head_batch", "fused_head_batch_ref",
           "tile_change_mask", "tile_change_mask_ref",
           "changed_window_map", "changed_window_map_ref"]


def _pad_to(x: jax.Array, mh: int, mw: int, mode: str = "edge") -> jax.Array:
    h, w = x.shape[-2:]
    ph = (-h) % mh
    pw = (-w) % mw
    if ph == 0 and pw == 0:
        return x
    cfg = [(0, 0)] * (x.ndim - 2) + [(0, ph), (0, pw)]
    return jnp.pad(x, cfg, mode=mode)


@partial(jax.jit, static_argnames=("tile", "use_kernel"))
def integral_image(img: jax.Array, *, tile=DEFAULT_TILE,
                   use_kernel: bool = True) -> jax.Array:
    """Padded SAT (H+1, W+1) of ``img`` — kernel-accelerated version of
    :func:`repro.core.integral.integral_image`."""
    h, w = img.shape
    if not use_kernel:
        ii = ref.integral_image_ref(img)
    else:
        padded = _pad_to(img.astype(jnp.float32), tile[0], tile[1],
                         mode="constant")
        ii = integral_image_kernel(padded, tile=tile,
                                   interpret=interpret_mode())[:h, :w]
    return jnp.pad(ii, ((1, 0), (1, 0)))


@partial(jax.jit, static_argnames=("ny", "nx", "tile", "use_kernel"))
def window_inv_sigma_grid(ii_pair: jax.Array, ny: int, nx: int, *,
                          tile=DEFAULT_TILE,
                          use_kernel: bool = True) -> jax.Array:
    """(ny, nx) 1/sigma grid from the stacked (ii2, iic) padded SAT pair."""
    ii2, iic = ii_pair[0], ii_pair[1]
    if not use_kernel:
        return ref.window_inv_sigma_ref(ii2, iic, ny, nx)
    ty, tx = tile
    ny_pad = ny + ((-ny) % ty)
    nx_pad = nx + ((-nx) % tx)
    need_h, need_w = sat_pad_shape(ny_pad, nx_pad)
    pad_h = max(0, need_h - ii2.shape[0])
    pad_w = max(0, need_w - ii2.shape[1])
    ii2p = jnp.pad(ii2, ((0, pad_h), (0, pad_w)), mode="edge")
    iicp = jnp.pad(iic, ((0, pad_h), (0, pad_w)), mode="edge")
    out = window_inv_sigma_kernel(ii2p, iicp, ny_pad, nx_pad, tile=tile,
                                  interpret=interpret_mode())
    return out[:ny, :nx]


def dense_stage_sums(cascade: Cascade, cascade_static: Cascade, s: int,
                     ii: jax.Array, inv_sigma_grid: jax.Array, *,
                     tile=DEFAULT_TILE) -> jax.Array:
    """Stage-``s`` vote sums over the dense stride-1 window grid.

    ``cascade`` carries (possibly traced) parameter arrays; the *static*
    twin provides the stage boundaries needed to slice them at trace time.
    """
    k0 = int(np.asarray(cascade_static.stage_offsets)[s])
    k1 = int(np.asarray(cascade_static.stage_offsets)[s + 1])
    ny, nx = inv_sigma_grid.shape
    ty, tx = tile
    ny_pad = ny + ((-ny) % ty)
    nx_pad = nx + ((-nx) % tx)
    need_h, need_w = sat_pad_shape(ny_pad, nx_pad)
    pad_h = max(0, need_h - ii.shape[0])
    pad_w = max(0, need_w - ii.shape[1])
    iip = jnp.pad(ii, ((0, pad_h), (0, pad_w)), mode="edge")
    invp = jnp.pad(inv_sigma_grid,
                   ((0, ny_pad - ny), (0, nx_pad - nx)), mode="edge")
    out = haar_stage_sums_kernel(
        cascade.rect_xywh[k0:k1], cascade.rect_w[k0:k1],
        cascade.wc_threshold[k0:k1], cascade.left_val[k0:k1],
        cascade.right_val[k0:k1], iip, invp, tile=tile,
        interpret=interpret_mode())
    return out[:ny, :nx]


def dense_stage_sums_ref(cascade: Cascade, cascade_static: Cascade, s: int,
                         ii: jax.Array, inv_sigma_grid: jax.Array
                         ) -> jax.Array:
    """Oracle twin of :func:`dense_stage_sums` (same signature contract)."""
    k0 = int(np.asarray(cascade_static.stage_offsets)[s])
    k1 = int(np.asarray(cascade_static.stage_offsets)[s + 1])
    return ref.dense_stage_sums_ref(
        cascade.rect_xywh[k0:k1], cascade.rect_w[k0:k1],
        cascade.wc_threshold[k0:k1], cascade.left_val[k0:k1],
        cascade.right_val[k0:k1], ii, inv_sigma_grid)


# ------------------------------------------------------------------ batched
# Leading-B-axis twins of the wrappers above, used by the batched detection
# head (Detector._build_batch_fn with use_pallas=True).  Implemented as
# jax.vmap over the kernels — Pallas lifts the mapped axis into an extra
# grid dimension, so one dispatch covers the whole stack — with the tile
# padding hoisted out so it is computed once per call, not once per image.
# Oracle twins live in kernels/ref.py (``*_batch_ref``).

@partial(jax.jit, static_argnames=("tile", "use_kernel"))
def integral_image_batch(imgs: jax.Array, *, tile=DEFAULT_TILE,
                         use_kernel: bool = True) -> jax.Array:
    """(B, H, W) -> (B, H+1, W+1) padded SATs (batched
    :func:`integral_image`, same per-image contract)."""
    _, h, w = imgs.shape
    if not use_kernel:
        ii = ref.integral_image_batch_ref(imgs)
    else:
        padded = _pad_to(imgs.astype(jnp.float32), tile[0], tile[1],
                         mode="constant")
        ii = jax.vmap(lambda im: integral_image_kernel(
            im, tile=tile, interpret=interpret_mode()))(padded)[:, :h, :w]
    return jnp.pad(ii, ((0, 0), (1, 0), (1, 0)))


@partial(jax.jit, static_argnames=("ny", "nx", "tile", "use_kernel"))
def window_inv_sigma_grid_batch(ii_pairs: jax.Array, ny: int, nx: int, *,
                                tile=DEFAULT_TILE,
                                use_kernel: bool = True) -> jax.Array:
    """(B, ny, nx) 1/sigma grids from stacked (B, 2, H+1, W+1) SAT pairs
    (batched :func:`window_inv_sigma_grid`, same per-image contract)."""
    ii2, iic = ii_pairs[:, 0], ii_pairs[:, 1]
    if not use_kernel:
        return ref.window_inv_sigma_batch_ref(ii2, iic, ny, nx)
    ty, tx = tile
    ny_pad = ny + ((-ny) % ty)
    nx_pad = nx + ((-nx) % tx)
    need_h, need_w = sat_pad_shape(ny_pad, nx_pad)
    pad_h = max(0, need_h - ii2.shape[1])
    pad_w = max(0, need_w - ii2.shape[2])
    cfg = ((0, 0), (0, pad_h), (0, pad_w))
    ii2p = jnp.pad(ii2, cfg, mode="edge")
    iicp = jnp.pad(iic, cfg, mode="edge")
    out = jax.vmap(lambda a, b: window_inv_sigma_kernel(
        a, b, ny_pad, nx_pad, tile=tile, interpret=interpret_mode())
                   )(ii2p, iicp)
    return out[:, :ny, :nx]


def dense_stage_sums_batch(cascade: Cascade, cascade_static: Cascade, s: int,
                           ii: jax.Array, inv_sigma_grid: jax.Array, *,
                           tile=DEFAULT_TILE) -> jax.Array:
    """(B, ny, nx) stage-``s`` vote sums over a stack of dense stride-1
    window grids — batched :func:`dense_stage_sums`: ``ii`` is (B, H+1, W+1)
    padded SATs, ``inv_sigma_grid`` is (B, ny, nx)."""
    k0 = int(np.asarray(cascade_static.stage_offsets)[s])
    k1 = int(np.asarray(cascade_static.stage_offsets)[s + 1])
    ny, nx = inv_sigma_grid.shape[1:]
    ty, tx = tile
    ny_pad = ny + ((-ny) % ty)
    nx_pad = nx + ((-nx) % tx)
    need_h, need_w = sat_pad_shape(ny_pad, nx_pad)
    pad_h = max(0, need_h - ii.shape[1])
    pad_w = max(0, need_w - ii.shape[2])
    iip = jnp.pad(ii, ((0, 0), (0, pad_h), (0, pad_w)), mode="edge")
    invp = jnp.pad(inv_sigma_grid,
                   ((0, 0), (0, ny_pad - ny), (0, nx_pad - nx)), mode="edge")
    out = jax.vmap(lambda ii_b, inv_b: haar_stage_sums_kernel(
        cascade.rect_xywh[k0:k1], cascade.rect_w[k0:k1],
        cascade.wc_threshold[k0:k1], cascade.left_val[k0:k1],
        cascade.right_val[k0:k1], ii_b, inv_b, tile=tile,
        interpret=interpret_mode()))(iip, invp)
    return out[:, :ny, :nx]


# -------------------------------------------------------------------- fused
# One-dispatch head: the vote sums of every stage of the run from a single
# fused_head_kernel call (kernels/fused_head.py), with the SAT and 1/sigma
# grid — the split path's own XLA ops — resident in VMEM, and early exit
# per (ty, tx) tile on the run's stage thresholds.  Where a tile entered a
# stage its sums are the split path's bits (integral_images ->
# window_inv_sigma -> one dense_stage_sums dispatch per stage, what
# Detector executes when the plan's head mode is "split"); elsewhere -inf.

def fused_head(cascade: Cascade, cascade_static: Cascade, s0: int, s1: int,
               img: jax.Array, *, tile=DEFAULT_TILE, live=None):
    """Fused head for stages ``[s0, s1)`` over one image.

    Returns ``(ii, inv_sigma_grid, stage_sums)``: the (H+1, W+1) padded
    int32 SAT (feeds the compacted tail's gathers), the (ny, nx) 1/sigma
    grid, and (s1 - s0, ny, nx) per-stage vote sums — the split path's
    wherever the window's tile entered the stage under
    ``cascade.stage_threshold``, ``-inf`` where it did not.  ``live``
    ((ny, nx) bool) selects the masked variant: tiles start from their
    live windows only, so a tile with none runs no stage.
    """
    k0, k1, rel = _stage_run_slices(cascade_static, s0, s1)
    return fused_head_kernel(
        cascade.rect_xywh[k0:k1], cascade.rect_w[k0:k1],
        cascade.wc_threshold[k0:k1], cascade.left_val[k0:k1],
        cascade.right_val[k0:k1], cascade.stage_threshold[s0:s1], rel, img,
        tile=tile, interpret=interpret_mode(), live=live)


def fused_head_ref(cascade: Cascade, cascade_static: Cascade, s0: int,
                   s1: int, img: jax.Array, *, tile=DEFAULT_TILE, live=None):
    """Oracle twin of :func:`fused_head` (same signature contract)."""
    k0, k1, rel = _stage_run_slices(cascade_static, s0, s1)
    return ref.fused_head_ref(
        cascade.rect_xywh[k0:k1], cascade.rect_w[k0:k1],
        cascade.wc_threshold[k0:k1], cascade.left_val[k0:k1],
        cascade.right_val[k0:k1], cascade.stage_threshold[s0:s1], rel, img,
        tile=tile, live=live)


def fused_head_batch(cascade: Cascade, cascade_static: Cascade, s0: int,
                     s1: int, imgs: jax.Array, *, tile=DEFAULT_TILE):
    """(B, H, W) stack -> batched :func:`fused_head` (same per-image
    contract, each image's tiles exiting on their own): ``(B, H+1, W+1)``
    SATs, ``(B, ny, nx)`` 1/sigma grids, ``(B, s1-s0, ny, nx)`` stage
    sums.  vmap lifts the batch axis into an extra Pallas grid dimension,
    so one dispatch covers the stack."""
    k0, k1, rel = _stage_run_slices(cascade_static, s0, s1)
    return jax.vmap(lambda im: fused_head_kernel(
        cascade.rect_xywh[k0:k1], cascade.rect_w[k0:k1],
        cascade.wc_threshold[k0:k1], cascade.left_val[k0:k1],
        cascade.right_val[k0:k1], cascade.stage_threshold[s0:s1], rel, im,
        tile=tile, interpret=interpret_mode()))(imgs.astype(jnp.float32))


def fused_head_batch_ref(cascade: Cascade, cascade_static: Cascade, s0: int,
                         s1: int, imgs: jax.Array, *, tile=DEFAULT_TILE):
    """Oracle twin of :func:`fused_head_batch` (same signature contract)."""
    k0, k1, rel = _stage_run_slices(cascade_static, s0, s1)
    return ref.fused_head_batch_ref(
        cascade.rect_xywh[k0:k1], cascade.rect_w[k0:k1],
        cascade.wc_threshold[k0:k1], cascade.left_val[k0:k1],
        cascade.right_val[k0:k1], cascade.stage_threshold[s0:s1], rel, imgs,
        tile=tile)


# ------------------------------------------------------------------- packed
# Packed-window stage-run kernel: the compacted tail's counterpart of
# dense_stage_sums.  Callers see natural shapes — an arbitrary-length packed
# window list in, (n_stages_run, cap) stage sums out; lane-block padding to
# the (8, 128) tile is hoisted here, mirroring the dense wrappers' tile
# padding contract.  The oracle twin packed_stage_sums_ref has the same
# signature; both are bit-identical to the gather backends in packed_tail.

def _stage_run_slices(cascade_static: Cascade, s0: int, s1: int):
    bounds = np.asarray(cascade_static.stage_offsets)
    k0, k1 = int(bounds[s0]), int(bounds[s1])
    rel = tuple(int(b) - k0 for b in bounds[s0:s1 + 1])
    return k0, k1, rel


def packed_stage_sums(cascade: Cascade, cascade_static: Cascade, s0: int,
                      s1: int, ii_flat: jax.Array, img: jax.Array,
                      base: jax.Array, stride: jax.Array, ys: jax.Array,
                      xs: jax.Array, inv_sigma: jax.Array, *,
                      tile=DEFAULT_TILE) -> jax.Array:
    """Stage sums for stages ``[s0, s1)`` over a packed window list.

    ``ii_flat`` is (B, sum_l (h_l+1)*(w_l+1)) — every level's SAT flattened
    and concatenated per image; ``img``/``base``/``stride`` address each
    window's level SAT, ``ys``/``xs`` are window origins at that level.
    Returns (s1 - s0, cap) float32 — one row of vote sums per stage, each
    bit-identical to the gather oracle on every lane.
    """
    k0, k1, rel = _stage_run_slices(cascade_static, s0, s1)
    cap = ys.shape[0]
    ty, tx = tile
    blk = ty * tx
    cap_pad = cap + ((-cap) % blk)
    n_rows = cap_pad // tx

    n_sat = ii_flat.shape[1]
    sat_flat = ii_flat.reshape(1, -1)
    # absolute flat offsets fold the image index away: one 1-D address space
    # for every (image, level) SAT, so the kernel's loads are single-index
    off = img.astype(jnp.int32) * n_sat + base.astype(jnp.int32)

    def blocks(v, dtype):
        v = jnp.pad(v.astype(dtype), (0, cap_pad - cap))
        return v.reshape(n_rows, tx)

    out = packed_stage_sums_kernel(
        cascade.rect_xywh[k0:k1], cascade.rect_w[k0:k1],
        cascade.wc_threshold[k0:k1], cascade.left_val[k0:k1],
        cascade.right_val[k0:k1], rel, sat_flat,
        blocks(off, jnp.int32), blocks(stride, jnp.int32),
        blocks(ys, jnp.int32), blocks(xs, jnp.int32),
        blocks(inv_sigma, jnp.float32), tile=tile, interpret=interpret_mode())
    return out.reshape(s1 - s0, cap_pad)[:, :cap]


def packed_stage_sums_ref(cascade: Cascade, cascade_static: Cascade, s0: int,
                          s1: int, ii_flat: jax.Array, img: jax.Array,
                          base: jax.Array, stride: jax.Array, ys: jax.Array,
                          xs: jax.Array, inv_sigma: jax.Array) -> jax.Array:
    """Oracle twin of :func:`packed_stage_sums` (same signature contract)."""
    k0, _k1, rel = _stage_run_slices(cascade_static, s0, s1)
    return ref.packed_stage_sums_ref(
        cascade.rect_xywh, cascade.rect_w, cascade.wc_threshold,
        cascade.left_val, cascade.right_val, k0, rel, ii_flat, img, base,
        stride, ys, xs, inv_sigma)


def dense_stage_sums_batch_ref(cascade: Cascade, cascade_static: Cascade,
                               s: int, ii: jax.Array,
                               inv_sigma_grid: jax.Array) -> jax.Array:
    """Oracle twin of :func:`dense_stage_sums_batch` (same contract)."""
    k0 = int(np.asarray(cascade_static.stage_offsets)[s])
    k1 = int(np.asarray(cascade_static.stage_offsets)[s + 1])
    return ref.dense_stage_sums_batch_ref(
        cascade.rect_xywh[k0:k1], cascade.rect_w[k0:k1],
        cascade.wc_threshold[k0:k1], cascade.left_val[k0:k1],
        cascade.right_val[k0:k1], ii, inv_sigma_grid)


@partial(jax.jit, static_argnames=("tile", "halo", "exact", "use_kernel"))
def tile_change_mask(prev: jax.Array, cur: jax.Array, threshold=0.0, *,
                     tile: int, halo: int = 0, exact: bool = True,
                     use_kernel: bool = True
                     ) -> tuple[jax.Array, jax.Array]:
    """(changed, scores) tile grids of ``cur`` vs ``prev`` — the device
    port of the host ``tile_change_scores`` + ``dilate_tiles`` pair
    (one fused pass: SAT scoring, exact/threshold test, halo dilation)."""
    if not use_kernel:
        return ref.tile_change_mask_ref(prev, cur, threshold, tile=tile,
                                        halo=halo, exact=exact)
    return tile_change_mask_kernel(prev, cur, threshold, tile=tile,
                                   halo=halo, exact=exact)


@partial(jax.jit, static_argnames=("use_kernel",))
def changed_window_map(changed: jax.Array, ty0: jax.Array, ty1: jax.Array,
                       tx0: jax.Array, tx1: jax.Array, valid: jax.Array,
                       *, use_kernel: bool = True) -> jax.Array:
    """Flat per-level window recompute mask from a changed-tile grid and
    the plan-compiled receptive-field tile-range brackets — the device
    port of the host ``changed_window_mask`` (integer SAT, exact)."""
    if not use_kernel:
        return ref.changed_window_map_ref(changed, ty0, ty1, tx0, tx1,
                                          valid)
    return changed_window_map_kernel(changed, ty0, ty1, tx0, tx1, valid)
