"""Device-side temporal tile planning: change scoring + window mapping.

Two kernels, with oracle twins in :mod:`repro.kernels.ref`
(``tile_change_mask_ref``, ``changed_window_map_ref``), so the
device-resident stream step (:meth:`repro.stream.StreamEngine.stream_step`)
can compute a whole frame plan without a host round-trip:

- :func:`tile_change_mask_kernel` — per-tile change scores as per-tile
  reductions of the squared frame delta, the exact/thresholded changed
  mask, and the halo dilation, in one pass;
- :func:`changed_window_map_kernel` — the changed-tile -> window range-OR
  per pyramid level, answered with two boolean range-indicator matmuls
  (exact: 0/1 operands, and only ``> 0`` is read of each product).

Neither gathers: both run as reductions, compares and matmuls, which the
TPU streams, where an XLA gather serializes (ROADMAP 1.3a).

Geometry never originates here: the per-level receptive-field tile-range
tables and window-limit masks are compiled once by
:func:`repro.plan.compile_stream_plan` and passed in as arrays
(PLAN_GEOMETRY).  With ``exact=True`` the changed test is a per-tile
any-reduction of ``delta != 0`` — IEEE subtraction is exact at zero
(``RN(x - y) == 0`` iff ``x == y``), so a tiny change is never absorbed
by a large one.  Positive-threshold *scores* are float32 sums, so
near-threshold tiles may classify differently from an exact-arithmetic
score (threshold 0 is the bit-identity contract).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["tile_change_mask_kernel", "changed_window_map_kernel"]


def tile_change_mask_kernel(prev: jax.Array, cur: jax.Array,
                            threshold: jax.Array, *, tile: int,
                            halo: int = 0, exact: bool = True
                            ) -> tuple[jax.Array, jax.Array]:
    """(changed, scores) over the tile grid of ``cur`` vs ``prev``.

    ``changed`` is the halo-dilated boolean tile mask (exact
    any-pixel-differs when ``exact``, else ``scores > threshold``);
    ``scores`` is the mean squared pixel change per tile.  Shapes are
    static from ``cur``; partial edge tiles divide by their true area,
    like the oracle twin.
    """
    h, w = cur.shape
    ty, tx = -(-h // tile), -(-w // tile)
    d = cur.astype(jnp.float32) - prev.astype(jnp.float32)
    pad = ((0, ty * tile - h), (0, tx * tile - w))
    sums = jnp.pad(d * d, pad).reshape(ty, tile, tx, tile).sum(axis=(1, 3))
    ys = np.minimum(np.arange(ty + 1) * tile, h)
    xs = np.minimum(np.arange(tx + 1) * tile, w)
    areas = (np.diff(ys)[:, None] * np.diff(xs)[None, :]).astype(np.float32)
    scores = sums / np.maximum(areas, 1.0)

    if exact:
        nz = jnp.pad(d != 0.0, pad)
        changed = nz.reshape(ty, tile, tx, tile).any(axis=(1, 3))
    else:
        changed = scores > threshold
    for _ in range(halo):          # 4-neighbour ring, like the oracle twin
        changed = (changed
                   | jnp.pad(changed[:-1, :], ((1, 0), (0, 0)))
                   | jnp.pad(changed[1:, :], ((0, 1), (0, 0)))
                   | jnp.pad(changed[:, :-1], ((0, 0), (1, 0)))
                   | jnp.pad(changed[:, 1:], ((0, 0), (0, 1))))
    return changed, scores


def changed_window_map_kernel(changed: jax.Array, ty0: jax.Array,
                              ty1: jax.Array, tx0: jax.Array,
                              tx1: jax.Array, valid: jax.Array
                              ) -> jax.Array:
    """Flat (ny*nx,) bool mask of windows overlapping a changed tile.

    ``ty0/ty1`` (ny,) and ``tx0/tx1`` (nx,) are the closed tile-range
    brackets of each window origin's receptive field (compiled host-side
    by the plan layer); ``valid`` is the flat window-limit mask.  The
    range-OR is two matmuls with the (ny, ty) and (nx, tx) indicator
    matrices of those ranges: rows that meet a changed tile, then windows
    whose columns meet one of those rows — exact, since every operand is
    0 or 1 and only ``> 0`` is read.
    """
    n_ty, n_tx = changed.shape

    def indicator(lo, hi, n):
        t = jnp.arange(n)[None, :]
        return ((t >= lo[:, None]) & (t <= hi[:, None])).astype(jnp.float32)

    ry = indicator(ty0, ty1, n_ty)                      # (ny, n_ty)
    rx = indicator(tx0, tx1, n_tx)                      # (nx, n_tx)
    rows = jnp.dot(ry, changed.astype(jnp.float32),
                   preferred_element_type=jnp.float32) > 0     # (ny, n_tx)
    cnt = jnp.dot(rows.astype(jnp.float32), rx.T,
                  preferred_element_type=jnp.float32)          # (ny, nx)
    return (cnt > 0).reshape(-1) & valid
