"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``<name>_ref`` matches the corresponding kernel's public wrapper in
``ops.py`` bit-for-bit in semantics (tests sweep shapes/dtypes and
``assert_allclose`` kernel vs oracle).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.cascade import WINDOW
from repro.core.integral import CENTRE, pixels, rect_sum

from .autotune import DEFAULT_TILE

_AREA = float(WINDOW * WINDOW)


def integral_image_ref(img: jax.Array) -> jax.Array:
    """Inclusive 2-D cumulative sum (unpadded), float32 — the contract of
    the standalone scan kernel (:mod:`repro.kernels.integral_image`)."""
    img = img.astype(jnp.float32)
    return jnp.cumsum(jnp.cumsum(img, axis=0), axis=1)


def _int_sat(x: jax.Array) -> jax.Array:
    """Padded (H+1, W+1) int32 summed-area table of whole-number pixels,
    wrapping like :func:`repro.core.integral.integral_image`."""
    return jnp.pad(jnp.cumsum(jnp.cumsum(x, axis=0), axis=1),
                   ((1, 0), (1, 0)))


def window_inv_sigma_ref(ii2: jax.Array, iic: jax.Array, ny: int, nx: int,
                         window: int = WINDOW) -> jax.Array:
    """(ny, nx) grid of 1/sigma per window origin (stride 1).

    ii2/iic are *padded* int32 SATs of the centred-squared / centred
    image (see repro.core.integral.integral_images).
    """
    n = float(window * window)
    ys = jnp.arange(ny)[:, None]
    xs = jnp.arange(nx)[None, :]
    s2 = rect_sum(ii2, ys, xs, window, window)
    s1 = rect_sum(iic, ys, xs, window, window)
    var = s2 / n - (s1 / n) ** 2
    return 1.0 / jnp.sqrt(jnp.maximum(var, 1.0))


def dense_stage_sums_ref(rect_xywh: jax.Array, rect_w: jax.Array,
                         wc_threshold: jax.Array, left_val: jax.Array,
                         right_val: jax.Array, ii: jax.Array,
                         inv_sigma: jax.Array) -> jax.Array:
    """Stage sums over a dense stride-1 window grid.

    rect_xywh (K,3,4), rect_w (K,3), thresholds/votes (K,): the stage's
    weak classifiers.  ii is the padded SAT; inv_sigma is the (ny, nx)
    normalization grid.  Returns (ny, nx) float32 stage sums.
    """
    ny, nx = inv_sigma.shape
    ys = jnp.arange(ny)[:, None]
    xs = jnp.arange(nx)[None, :]

    def body(k, acc):
        rects = jax.lax.dynamic_index_in_dim(rect_xywh, k, 0, False)
        w = jax.lax.dynamic_index_in_dim(rect_w, k, 0, False)
        feat = jnp.zeros((ny, nx), jnp.float32)
        for r in range(rects.shape[0]):
            rx, ry = rects[r, 0], rects[r, 1]
            rw_, rh = rects[r, 2], rects[r, 3]
            feat = feat + w[r] * rect_sum(ii, ys + ry, xs + rx, rh, rw_)
        f_norm = feat * inv_sigma / _AREA
        vote = jnp.where(f_norm < wc_threshold[k], left_val[k], right_val[k])
        return acc + vote

    init = jnp.zeros((ny, nx), jnp.float32)
    return jax.lax.fori_loop(0, rect_xywh.shape[0], body, init)


# ----------------------------------------------------------------- fused
def tile_exit_ref(sums: jax.Array, stage_threshold: jax.Array,
                  tile=DEFAULT_TILE, live: jax.Array | None = None
                  ) -> jax.Array:
    """Dense (n_run, ny, nx) stage sums with the fused head's early exit
    per (ty, tx) tile applied: a stage's sums are kept where some window
    of the tile is still alive entering it (``live``, default every
    window, and ``sums >= stage_threshold`` at every earlier stage of the
    run), and read ``-inf`` elsewhere."""
    n_run, ny, nx = sums.shape
    ty, tx = tile
    nty, ntx = -(-ny // ty), -(-nx // tx)
    pad = ((0, nty * ty - ny), (0, ntx * tx - nx))
    alive = (jnp.ones((ny, nx), bool) if live is None
             else jnp.asarray(live, bool))
    out = []
    for s in range(n_run):
        entered = jnp.pad(alive, pad).reshape(nty, ty, ntx, tx).any((1, 3))
        entered = jnp.repeat(jnp.repeat(entered, ty, 0), tx, 1)[:ny, :nx]
        out.append(jnp.where(entered, sums[s], -jnp.inf))
        alive = alive & (sums[s] >= stage_threshold[s])
    return jnp.stack(out)


def fused_head_ref(rect_xywh: jax.Array, rect_w: jax.Array,
                   wc_threshold: jax.Array, left_val: jax.Array,
                   right_val: jax.Array, stage_threshold: jax.Array,
                   rel_bounds: tuple, img: jax.Array, *, tile=DEFAULT_TILE,
                   live: jax.Array | None = None):
    """Oracle twin of the fused head megakernel (kernels/fused_head.py):
    the split path composed from this module's own pieces, then
    :func:`tile_exit_ref`.  The weak-classifier arrays and
    ``stage_threshold`` cover one stage run; ``rel_bounds`` are its
    per-stage boundaries; ``live`` is the masked variant's (ny, nx)
    initial alive mask.  Returns ``(ii, inv_sigma, sums)`` — the
    (H+1, W+1) padded int32 SAT, the (ny, nx) 1/sigma grid, and
    (n_run, ny, nx) per-stage vote sums, ``-inf`` where the window's tile
    exited before.
    """
    x = pixels(img)
    h, w = x.shape
    ny, nx = h - WINDOW + 1, w - WINDOW + 1
    ii = _int_sat(x)
    centred = x - CENTRE
    ii2 = _int_sat(centred * centred)
    iic = _int_sat(centred)
    inv = window_inv_sigma_ref(ii2, iic, ny, nx)
    sums = jnp.stack([
        dense_stage_sums_ref(rect_xywh[a:b], rect_w[a:b],
                             wc_threshold[a:b], left_val[a:b],
                             right_val[a:b], ii, inv)
        for a, b in zip(rel_bounds[:-1], rel_bounds[1:])])
    return ii, inv, tile_exit_ref(sums, stage_threshold, tile, live)


def fused_head_batch_ref(rect_xywh: jax.Array, rect_w: jax.Array,
                         wc_threshold: jax.Array, left_val: jax.Array,
                         right_val: jax.Array, stage_threshold: jax.Array,
                         rel_bounds: tuple, imgs: jax.Array, *,
                         tile=DEFAULT_TILE):
    """(B, H, W) stack -> per-image :func:`fused_head_ref` (oracle twin of
    the batched fused-head wrapper, same per-image contract)."""
    return jax.vmap(lambda im: fused_head_ref(
        rect_xywh, rect_w, wc_threshold, left_val, right_val,
        stage_threshold, rel_bounds, im, tile=tile))(imgs)


# ---------------------------------------------------------------- packed
def packed_stage_sums_ref(rect_xywh: jax.Array, rect_w: jax.Array,
                          wc_threshold: jax.Array, left_val: jax.Array,
                          right_val: jax.Array, k0: int, rel_bounds: tuple,
                          ii_flat: jax.Array, img: jax.Array,
                          base: jax.Array, stride: jax.Array, ys: jax.Array,
                          xs: jax.Array, inv_sigma: jax.Array) -> jax.Array:
    """(n_run, cap) stage sums over a packed window list — the gather
    oracle of the packed-window kernel.

    ``ii_flat`` is (B, S) flattened per-level SATs; each window is
    addressed through ``(img, base + y*stride + x)``.  ``rel_bounds`` are
    the run's stage boundaries relative to ``k0``.  Per-lane arithmetic is
    the wave engine's packed-tail reference: rectangle corners combined as
    ``d - b - c + a``, ``feat * inv_sigma / AREA`` normalization, weak
    votes summed in ascending-``k`` order.
    """

    def rect(y0, x0, rh, rw):
        y1, x1 = y0 + rh, x0 + rw
        return (ii_flat[img, base + y1 * stride + x1]
                - ii_flat[img, base + y0 * stride + x1]
                - ii_flat[img, base + y1 * stride + x0]
                + ii_flat[img, base + y0 * stride + x0]).astype(jnp.float32)

    def body(k, acc):
        rects = jax.lax.dynamic_index_in_dim(rect_xywh, k, 0, False)
        w = jax.lax.dynamic_index_in_dim(rect_w, k, 0, False)
        feat = jnp.zeros_like(ys, jnp.float32)
        for r in range(rects.shape[0]):
            rx, ry, rw_, rh = rects[r, 0], rects[r, 1], rects[r, 2], rects[r, 3]
            feat = feat + w[r] * rect(ys + ry, xs + rx, rh, rw_)
        f_norm = feat * inv_sigma / _AREA
        vote = jnp.where(f_norm < wc_threshold[k], left_val[k], right_val[k])
        return acc + vote

    init = jnp.zeros_like(ys, jnp.float32)
    return jnp.stack([
        jax.lax.fori_loop(k0 + rel_bounds[si], k0 + rel_bounds[si + 1],
                          body, init)
        for si in range(len(rel_bounds) - 1)])


# --------------------------------------------------------------- batched
# Oracle twins of the batched wrappers in ops.py: a leading B axis over the
# single-image references, so the batched kernels have the same bit-level
# contract per slice as their single-image counterparts.

def integral_image_batch_ref(imgs: jax.Array) -> jax.Array:
    """(B, H, W) -> (B, H, W) per-image inclusive 2-D cumsum (unpadded)."""
    return jax.vmap(integral_image_ref)(imgs)


def window_inv_sigma_batch_ref(ii2: jax.Array, iic: jax.Array, ny: int,
                               nx: int, window: int = WINDOW) -> jax.Array:
    """(B, ny, nx) 1/sigma grids from stacked (B, H+1, W+1) padded SATs."""
    return jax.vmap(lambda a, b: window_inv_sigma_ref(a, b, ny, nx, window)
                    )(ii2, iic)


def dense_stage_sums_batch_ref(rect_xywh: jax.Array, rect_w: jax.Array,
                               wc_threshold: jax.Array, left_val: jax.Array,
                               right_val: jax.Array, ii: jax.Array,
                               inv_sigma: jax.Array) -> jax.Array:
    """(B, ny, nx) stage sums: ``dense_stage_sums_ref`` over a leading B
    axis of SATs ``ii`` (B, H+1, W+1) and grids ``inv_sigma`` (B, ny, nx)."""
    return jax.vmap(lambda ii_b, inv_b: dense_stage_sums_ref(
        rect_xywh, rect_w, wc_threshold, left_val, right_val, ii_b, inv_b)
    )(ii, inv_sigma)


def window_inv_sigma_grid_ref(ii_pair: jax.Array, ny: int, nx: int,
                              window: int = WINDOW) -> jax.Array:
    """(ny, nx) 1/sigma grid from the stacked (2, H+1, W+1) padded SAT
    pair — oracle twin of :func:`repro.kernels.ops.window_inv_sigma_grid`
    (same stacked-pair calling convention, pure jnp)."""
    return window_inv_sigma_ref(ii_pair[0], ii_pair[1], ny, nx, window)


def window_inv_sigma_grid_batch_ref(ii_pairs: jax.Array, ny: int, nx: int,
                                    window: int = WINDOW) -> jax.Array:
    """(B, ny, nx) 1/sigma grids from stacked (B, 2, H+1, W+1) SAT pairs —
    oracle twin of :func:`repro.kernels.ops.window_inv_sigma_grid_batch`."""
    return window_inv_sigma_batch_ref(ii_pairs[:, 0], ii_pairs[:, 1],
                                      ny, nx, window)


# Oracle twins of the device tile-planning kernels (repro.kernels
# .tile_change): independent algorithms — SAT corner lookups (the paper's
# Fig. 4 arithmetic) instead of per-tile reductions, and an integer SAT
# instead of the range matmuls — so a reduction or indicator bug cannot
# hide in its own oracle.  Masks match bit-for-bit; float *scores* agree
# to summation-order tolerance (this oracle sums through a float32 SAT).

def tile_change_mask_ref(prev: jax.Array, cur: jax.Array,
                         threshold: jax.Array, *, tile: int, halo: int = 0,
                         exact: bool = True) -> tuple[jax.Array, jax.Array]:
    """(changed, scores) per tile via four SAT corner lookups per tile."""
    h, w = cur.shape
    ty, tx = -(-h // tile), -(-w // tile)
    d = cur.astype(jnp.float32) - prev.astype(jnp.float32)
    ys = jnp.minimum(jnp.arange(ty + 1) * tile, h)
    xs = jnp.minimum(jnp.arange(tx + 1) * tile, w)

    def tile_sums(x):
        sat = jnp.pad(jnp.cumsum(jnp.cumsum(x, axis=0), axis=1),
                      ((1, 0), (1, 0)))
        c = sat[ys[:, None], xs[None, :]]
        return c[1:, 1:] - c[:-1, 1:] - c[1:, :-1] + c[:-1, :-1]

    area = (jnp.diff(ys)[:, None] * jnp.diff(xs)[None, :]
            ).astype(jnp.float32)
    scores = tile_sums(d * d) / jnp.maximum(area, 1.0)
    if exact:
        changed = tile_sums((d != 0.0).astype(jnp.int32)) > 0
    else:
        changed = scores > threshold
    for _ in range(halo):
        changed = (changed
                   | jnp.pad(changed[:-1, :], ((1, 0), (0, 0)))
                   | jnp.pad(changed[1:, :], ((0, 1), (0, 0)))
                   | jnp.pad(changed[:, :-1], ((0, 0), (1, 0)))
                   | jnp.pad(changed[:, 1:], ((0, 0), (0, 1))))
    return changed, scores


def changed_window_map_ref(changed: jax.Array, ty0: jax.Array,
                           ty1: jax.Array, tx0: jax.Array, tx1: jax.Array,
                           valid: jax.Array) -> jax.Array:
    """Flat window mask via an integer SAT over the changed-tile grid."""
    sat = jnp.pad(jnp.cumsum(jnp.cumsum(changed.astype(jnp.int32), axis=0),
                             axis=1), ((1, 0), (1, 0)))
    y1, x1 = (ty1 + 1)[:, None], (tx1 + 1)[None, :]
    y0, x0 = ty0[:, None], tx0[None, :]
    cnt = sat[y1, x1] - sat[y0, x1] - sat[y1, x0] + sat[y0, x0]
    return (cnt > 0).reshape(-1) & valid
