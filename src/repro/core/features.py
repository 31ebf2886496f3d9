"""Haar-feature / weak-classifier / stage evaluation — pure-jnp oracle.

These functions are the semantic reference for the Pallas kernels in
``repro.kernels`` and the gather-based "tail" path of the wave engine
(compacted windows in late cascade stages, where occupancy is low and a
dense tile kernel would waste VPU lanes).

All evaluators are vectorized over a 1-D list of window origins (ys, xs)
on one pyramid scale.  ``ii`` is the padded SAT from
:func:`repro.core.integral.integral_image`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .cascade import Cascade, WINDOW
from .integral import grid_rect_sum, rect_sum

__all__ = [
    "eval_weak_classifier",
    "stage_sum_windows",
    "stage_sum_grid",
    "run_sums_grid",
    "eval_stage",
    "run_cascade_windows",
]

_AREA = float(WINDOW * WINDOW)


def eval_weak_classifier(cascade: Cascade, k: jax.Array, ii: jax.Array,
                         ys: jax.Array, xs: jax.Array,
                         inv_sigma: jax.Array) -> jax.Array:
    """Vote of weak classifier ``k`` on each window (paper Eq. 1–2).

    The hot function of the paper's profile (Fig. 13: ~64–66% of runtime).
    """
    rects = jax.lax.dynamic_index_in_dim(cascade.rect_xywh, k, 0, False)
    w = jax.lax.dynamic_index_in_dim(cascade.rect_w, k, 0, False)
    feat = jnp.zeros_like(ys, jnp.float32)
    for r in range(rects.shape[0]):
        rx, ry, rw, rh = rects[r, 0], rects[r, 1], rects[r, 2], rects[r, 3]
        feat = feat + w[r] * rect_sum(ii, ys + ry, xs + rx, rh, rw)
    f_norm = feat * inv_sigma / _AREA
    theta = cascade.wc_threshold[k]
    return jnp.where(f_norm < theta, cascade.left_val[k],
                     cascade.right_val[k])


def stage_sum_windows(cascade: Cascade, ii: jax.Array, ys: jax.Array,
                      xs: jax.Array, inv_sigma: jax.Array,
                      k0: jax.Array, k1: jax.Array) -> jax.Array:
    """Sum of weak votes for classifiers [k0, k1) over each window.

    k0/k1 may be traced (stage bounds come from ``cascade.stage_offsets``),
    so this rolls a ``fori_loop``; the Pallas kernel unrolls the same loop
    per stage with scalar-prefetched parameters.
    """

    def body(k, acc):
        return acc + eval_weak_classifier(cascade, k, ii, ys, xs, inv_sigma)

    init = jnp.zeros_like(ys, jnp.float32)
    return jax.lax.fori_loop(k0, k1, body, init)


def stage_sum_grid(cascade: Cascade, ii: jax.Array, inv_sigma: jax.Array,
                   step: int, k0: jax.Array, k1: jax.Array) -> jax.Array:
    """:func:`stage_sum_windows` over a whole (ny, nx) grid of window
    origins ``(step * i, step * j)`` (``inv_sigma`` is that grid's
    1/sigma): every corner is a SAT slice (:func:`grid_rect_sum`), with
    the same float ordering, so the sums are bit-identical to the gather
    form.  Returns (ny, nx)."""
    ny, nx = inv_sigma.shape

    def body(k, acc):
        rects = jax.lax.dynamic_index_in_dim(cascade.rect_xywh, k, 0, False)
        w = jax.lax.dynamic_index_in_dim(cascade.rect_w, k, 0, False)
        feat = jnp.zeros((ny, nx), jnp.float32)
        for r in range(rects.shape[0]):
            rx, ry, rw, rh = rects[r, 0], rects[r, 1], rects[r, 2], rects[r, 3]
            feat = feat + w[r] * grid_rect_sum(ii, ny, nx, step, ry, rx, rh,
                                               rw)
        f_norm = feat * inv_sigma / _AREA
        return acc + jnp.where(f_norm < cascade.wc_threshold[k],
                               cascade.left_val[k], cascade.right_val[k])

    return jax.lax.fori_loop(k0, k1, body, jnp.zeros((ny, nx), jnp.float32))


def run_sums_grid(cascade: Cascade, ii: jax.Array, inv_sigma: jax.Array,
                  step: int, s0: int, s1: int) -> jax.Array:
    """(s1 - s0, ny, nx) :func:`stage_sum_grid` of every stage in
    ``[s0, s1)``, in one loop rolled over the stages, so the program holds
    one loop body however many stages the run has (the fused kernel's
    shape, and its output)."""
    ny, nx = inv_sigma.shape
    off = cascade.stage_offsets

    def stage(j, out):
        return out.at[j].set(stage_sum_grid(cascade, ii, inv_sigma, step,
                                            off[s0 + j], off[s0 + j + 1]))

    return jax.lax.fori_loop(0, s1 - s0, stage,
                             jnp.zeros((s1 - s0, ny, nx), jnp.float32))


def eval_stage(cascade: Cascade, s: int, ii: jax.Array, ys: jax.Array,
               xs: jax.Array, inv_sigma: jax.Array) -> jax.Array:
    """Boolean pass mask of stage ``s`` (static int) for each window."""
    k0 = cascade.stage_offsets[s]
    k1 = cascade.stage_offsets[s + 1]
    ss = stage_sum_windows(cascade, ii, ys, xs, inv_sigma, k0, k1)
    return ss >= cascade.stage_threshold[s]


@partial(jax.jit, static_argnames=("mode",))
def run_cascade_windows(cascade: Cascade, ii: jax.Array, ii_pair: jax.Array,
                        ys: jax.Array, xs: jax.Array,
                        mode: str = "early_exit"):
    """Full cascade over a window list.  Returns (accept_mask, exit_stage).

    mode="early_exit": per-window masked early exit (windows that fail a
      stage contribute no further work in the *scan sense* — on SIMD this
      is only a semantic reference; the engine's compaction makes it fast).
    mode="dense": the paper's §7.1 'delayed rejection' — every stage is
      evaluated for every window (breaks the inter-stage dependency, the
      paper-faithful parallel baseline).
    """
    from .integral import window_inv_sigma

    inv_sigma = window_inv_sigma(ii_pair, ys, xs, WINDOW)
    n_stages = cascade.n_stages
    alive = jnp.ones_like(ys, dtype=bool)
    exit_stage = jnp.full(ys.shape, n_stages, jnp.int32)

    def stage_body(s, carry):
        alive, exit_stage = carry
        k0 = cascade.stage_offsets[s]
        k1 = cascade.stage_offsets[s + 1]
        ss = stage_sum_windows(cascade, ii, ys, xs, inv_sigma, k0, k1)
        passed = ss >= cascade.stage_threshold[s]
        newly_dead = alive & ~passed
        exit_stage = jnp.where(newly_dead, s, exit_stage)
        if mode == "early_exit":
            alive = alive & passed
        else:  # dense / delayed rejection
            alive = alive & passed
        return alive, exit_stage

    # Both modes compute the same result; they differ in *scheduling* inside
    # the engine (this oracle always evaluates every stage's sums).
    for s in range(n_stages):
        alive, exit_stage = stage_body(s, (alive, exit_stage))
    return alive, exit_stage
