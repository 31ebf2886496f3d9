"""Fused Haar-head megakernel: every dense stage's vote sums, one dispatch.

The split head runs one ``haar_stage`` Pallas dispatch *per dense stage*
after the jnp SAT and 1/sigma, with HBM round-trips between them.
BENCH_detector shows that split head is the dominant cost of a batched
detect.  This kernel fuses the dense head into one ``pallas_call`` per
image: the summed-area table stays resident in VMEM (constant index map,
so it is copied in once), and every (ty, tx) tile of window origins
computes the vote sums of every stage its windows reach — the xformers
fused-softmax idiom (keep the row resident, do all the passes) applied
to SAT+cascade.

Early exit at tile granularity: each (ty, tx) tile starts from the real
window origins of the level and runs the stages in order while any of
them is alive, by the engine's own test ``sums >= stage_threshold``.  A
stage no window of the tile entered is not run, and its sums read
``-inf``: every window of that tile is already dead, and the engine's
cumulative ``alive & (sums >= threshold)`` chain keeps it dead, so its
survivors and per-stage counts are those of the dense head.

The masked variant (``live``, a (ny, nx) bool map) starts each tile from
its real origins that are also live: the stream step passes the map of
windows whose pixels changed, so a tile none of whose windows changed
stops after one mask check and writes ``-inf`` for every stage.  Both
variants share one kernel body (``masked`` is a ``functools.partial``
flag); without ``live`` the call and its lowering are the photo path's.

Bit-exactness contract (the engine's tests hold fused against split to
the last ulp wherever the tile entered the stage; thresholds that reject
nothing reproduce the dense split path everywhere, and the oracle twin
:func:`repro.kernels.ref.fused_head_ref` applies the same tile exit):

- SAT and 1/sigma: computed outside the kernel, in the same jitted
  program, by :func:`repro.core.integral.integral_images` and
  :func:`repro.core.integral.window_inv_sigma_grid` — the very XLA ops
  the split and jnp paths run, so they are identical on every backend.
  The SAT is int32 and wraps; the kernel differences its corners in
  int32 (exact) and converts each rectangle sum to float32 after.
  (A prefix sum has no Mosaic lowering, and Mosaic's sqrt/divide need
  not round as XLA's do.)
- stage sums of the stages a tile runs:
  :func:`repro.kernels.haar_stage.weak_vote` — the split kernel's own
  per-classifier body — accumulated in ascending k.

Valid window origins only ever read SAT rows/cols up to ``(h, w)`` — the
true (h+1, w+1) table — so the edge padding added for non-tile-aligned
grids never leaks into the ``[:ny, :nx]`` outputs the wrapper returns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cascade import WINDOW
from repro.core.integral import integral_images, window_inv_sigma_grid

from .autotune import DEFAULT_TILE
from .haar_stage import sat_pad_shape, weak_tables, weak_vote


def _fused_kernel(rx_ref, rw_ref, th_ref, lv_ref, rv_ref,  # SMEM (prefetch)
                  bd_ref, st_ref, ii_ref, inv_ref, *refs, n_run, tile,
                  ny, nx, masked):
    live_ref, o_ref = refs if masked else (None, refs[0])
    ty, tx = tile
    y0 = pl.program_id(0) * ty
    x0 = pl.program_id(1) * tx
    inv_sigma = inv_ref[...]

    def body(k, acc):
        return acc + weak_vote(k, rx_ref, rw_ref, th_ref, lv_ref, rv_ref,
                               ii_ref, inv_sigma, y0, x0, tile)

    # the tile's alive mask, int32 (Mosaic cannot carry a bool vector
    # through a loop): the origins that are real windows of the level, so
    # the edge padding never keeps a tile running, and live if masked
    iy = jax.lax.broadcasted_iota(jnp.int32, tile, 0)
    ix = jax.lax.broadcasted_iota(jnp.int32, tile, 1)
    real = (y0 + iy < ny) & (x0 + ix < nx)
    if masked:
        real = real & (live_ref[...] > 0)
    alive0 = real.astype(jnp.int32)

    def more(carry):
        si, alive = carry
        return (si < n_run) & (jnp.max(alive) > 0)

    # rolled over the run's stages (bounds from SMEM): one loop body to
    # lower and compile however many stages the run holds; the tile stops
    # at the first stage after which none of its windows is alive, by the
    # engine's own comparison
    def stage(carry):
        si, alive = carry
        sums = jax.lax.fori_loop(bd_ref[si], bd_ref[si + 1], body,
                                 jnp.zeros(tile, jnp.float32))
        o_ref[si] = sums
        return si + 1, jnp.where(sums >= st_ref[si], alive, 0)

    ran, _ = jax.lax.while_loop(more, stage, (jnp.int32(0), alive0))

    def skipped(si, carry):
        o_ref[si] = jnp.full(tile, -jnp.inf, jnp.float32)
        return carry

    jax.lax.fori_loop(ran, n_run, skipped, 0)


def fused_head_kernel(rect_xywh: jax.Array, rect_w: jax.Array,
                      wc_threshold: jax.Array, left_val: jax.Array,
                      right_val: jax.Array, stage_threshold: jax.Array,
                      rel_bounds: tuple, img: jax.Array, *,
                      tile=DEFAULT_TILE, interpret: bool,
                      live: jax.Array | None = None):
    """One-dispatch head over a full image, with early exit per tile.

    The weak-classifier arrays and ``stage_threshold`` cover stages
    ``[s0, s1)`` of the cascade (already sliced by the ops wrapper);
    ``rel_bounds`` are that run's stage boundaries relative to its first
    weak classifier.  ``live`` (optional, (ny, nx) bool) is the initial
    alive mask of the masked variant.  Returns ``(ii, inv_sigma, sums)``:
    the (H+1, W+1) padded int32 SAT (identical to
    ``integral_images(img)[0]`` — it feeds the tail's gathers), the
    (ny, nx) 1/sigma grid, and (n_run, ny, nx) per-stage vote sums — the
    split path's wherever the window's tile entered the stage, ``-inf``
    where it did not.  Handles non-tile-aligned grids by padding and
    slicing here.
    """
    h, w = img.shape
    ny = h - WINDOW + 1
    nx = w - WINDOW + 1
    assert ny > 0 and nx > 0, (h, w)
    ty, tx = tile
    ny_pad = ny + ((-ny) % ty)
    nx_pad = nx + ((-nx) % tx)
    hp, wp = sat_pad_shape(ny_pad, nx_pad)
    rel_bounds = tuple(int(b) for b in rel_bounds)
    n_run = len(rel_bounds) - 1
    assert n_run >= 1, rel_bounds

    ii, pair = integral_images(img)
    inv = window_inv_sigma_grid(pair, ny, nx, 1, WINDOW)
    # edge pad: SAT rows/cols and 1/sigma entries no valid window reads
    ii_p = jnp.pad(ii, ((0, hp - h - 1), (0, wp - w - 1)), mode="edge")
    inv_p = jnp.pad(inv, ((0, ny_pad - ny), (0, nx_pad - nx)), mode="edge")

    masked = live is not None
    tiles = [ii_p, inv_p]
    if masked:
        tiles.append(jnp.pad(live.astype(jnp.int32),
                             ((0, ny_pad - ny), (0, nx_pad - nx))))
    kernel = functools.partial(_fused_kernel, n_run=n_run, tile=tile,
                               ny=ny, nx=nx, masked=masked)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(ny_pad // ty, nx_pad // tx),
        in_specs=[
            # the SAT stays resident for the whole grid
            pl.BlockSpec((hp, wp), lambda i, j, *_: (0, 0)),
        ] + [pl.BlockSpec((ty, tx), lambda i, j, *_: (i, j))] * (
            len(tiles) - 1),
        out_specs=pl.BlockSpec((n_run, ty, tx), lambda i, j, *_: (0, i, j)),
    )
    sums = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_run, ny_pad, nx_pad), jnp.float32),
        interpret=interpret,
    )(*weak_tables(rect_xywh, rect_w, wc_threshold, left_val, right_val),
      jnp.asarray(rel_bounds, jnp.int32),
      stage_threshold.astype(jnp.float32), *tiles)
    return ii, inv, sums[:, :ny, :nx]
