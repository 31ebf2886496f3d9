"""The one packed-tail evaluator: compacted cascade stages, three backends.

Every "tail" in the system — the batched engine's shared-compaction
segments (``Detector._build_batch_fn``) — runs the same computation: a run
of cascade stages over a *packed* window list whose entries live on
different images and pyramid levels, addressed through flat per-level SAT
offsets.  This module is its single
implementation, with three interchangeable, bit-identical backends:

``gather``
    The fori-loop oracle (one weak classifier at a time, 12 tiny gathers
    per classifier).  Fewest operations in flight; wins when the packed
    list is tiny, and is the exactness referee for the other two.

``bulk``
    One *bulk* gather per rectangle corner across all ``K`` weak
    classifiers of a stage — 4 gathers of shape (K, 3, cap) instead of
    12·K scalarized ones.  The strong XLA default for mid-sized lists.

``pallas``
    The blocked packed-window kernel (:mod:`repro.kernels.packed_window`):
    lanes processed in (8, 128) blocks with the flat SAT resident per
    dispatch and the whole stage run evaluated per block.  Wins when the
    packed list is large (high survivor / changed-window density).

The dense/packed/gather *crossover* is a measured property, not a guess:
:func:`measure_rungs` times each backend at capacity-ladder sizes and
records the winner per rung; ``Detector.calibrated(tune_tail=True)``
persists that ladder in ``EngineConfig.tail_rungs`` so batched detection
and serving inherit one decision.  (The *dense* end of the
spectrum — full-grid waves through the dense tile kernel — is chosen
earlier, by the engine's segment plan; this module only arbitrates the
packed/gather end.)
"""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.cascade import Cascade, WINDOW

__all__ = ["BACKENDS", "stage_sums", "select_backend", "tail_backends",
           "measure_rungs"]

_AREA = float(WINDOW * WINDOW)

BACKENDS = ("gather", "bulk", "pallas")

# capacity-ladder sizes at which measure_rungs races the backends; chosen to
# bracket the real ladders (BATCH_CAP_FLOOR=128 .. calibrated batch lists)
DEFAULT_RUNG_SIZES = (128, 512, 2048, 8192)

# lanes per bulk-gather block: each (K, 3, lanes) corner temporary stays
# under ~K * 3 * 256 KiB, where a whole calibrated VGA batch list of
# millions of lanes would need gigabytes of device memory per gather
BULK_LANE_BLOCK = 1 << 16


def _gather_stage_sum(cascade: Cascade, ii_flat: jax.Array, img: jax.Array,
                      base: jax.Array, stride: jax.Array, ys: jax.Array,
                      xs: jax.Array, inv_sigma: jax.Array, k0, k1
                      ) -> jax.Array:
    """Stage sum over the packed list, one weak classifier at a time.

    The semantic reference: per-window arithmetic matches
    ``features.stage_sum_windows`` bit-for-bit — same rectangle
    accumulation order, same normalization — only the SAT lookup goes
    through the packed (img, base + y*stride + x) indexing.
    """

    def rect(y0, x0, rh, rw):
        y1, x1 = y0 + rh, x0 + rw
        return (ii_flat[img, base + y1 * stride + x1]
                - ii_flat[img, base + y0 * stride + x1]
                - ii_flat[img, base + y1 * stride + x0]
                + ii_flat[img, base + y0 * stride + x0]).astype(jnp.float32)

    def body(k, acc):
        rects = jax.lax.dynamic_index_in_dim(cascade.rect_xywh, k, 0, False)
        w = jax.lax.dynamic_index_in_dim(cascade.rect_w, k, 0, False)
        feat = jnp.zeros_like(ys, jnp.float32)
        for r in range(rects.shape[0]):
            rx, ry, rw, rh = rects[r, 0], rects[r, 1], rects[r, 2], rects[r, 3]
            feat = feat + w[r] * rect(ys + ry, xs + rx, rh, rw)
        f_norm = feat * inv_sigma / _AREA
        vote = jnp.where(f_norm < cascade.wc_threshold[k],
                         cascade.left_val[k], cascade.right_val[k])
        return acc + vote

    init = jnp.zeros_like(ys, jnp.float32)
    return jax.lax.fori_loop(k0, k1, body, init)


def _bulk_stage_sum(cascade: Cascade, ii_flat: jax.Array, img: jax.Array,
                    base: jax.Array, stride: jax.Array, ys: jax.Array,
                    xs: jax.Array, inv_sigma: jax.Array,
                    k0: int, k1: int) -> jax.Array:
    """Stage sum over packed windows, one *bulk* gather per rect corner.

    Bit-identical decisions to :func:`_gather_stage_sum` (same rectangle
    accumulation order, same normalization, weak votes summed in
    ascending-``k`` order), but restructured for XLA: instead of a
    ``fori_loop`` issuing 12 tiny gathers per weak classifier, all
    ``K = k1 - k0`` weak classifiers' corner lookups are batched into 4
    gathers of shape (K, 3, lanes).  Lists longer than
    :data:`BULK_LANE_BLOCK` run block by block (``lax.map``), so those
    temporaries stay bounded at any capacity.  ``k0``/``k1`` must be
    Python ints (stage bounds are static).
    """
    cap = ys.shape[0]
    if cap <= BULK_LANE_BLOCK:
        return _bulk_block(cascade, ii_flat, img, base, stride, ys, xs,
                           inv_sigma, k0, k1)
    n = -(-cap // BULK_LANE_BLOCK)

    def blocks(v):          # padded lanes address SAT entry 0; dropped below
        return jnp.pad(v, (0, n * BULK_LANE_BLOCK - cap)).reshape(
            n, BULK_LANE_BLOCK)

    out = jax.lax.map(
        lambda a: _bulk_block(cascade, ii_flat, *a, k0, k1),
        tuple(blocks(v) for v in (img, base, stride, ys, xs, inv_sigma)))
    return out.reshape(-1)[:cap]


def _bulk_block(cascade: Cascade, ii_flat: jax.Array, img: jax.Array,
                base: jax.Array, stride: jax.Array, ys: jax.Array,
                xs: jax.Array, inv_sigma: jax.Array,
                k0: int, k1: int) -> jax.Array:
    rects = cascade.rect_xywh[k0:k1]            # (K, 3, 4) int32
    w = cascade.rect_w[k0:k1]                   # (K, 3)
    rx = rects[:, :, 0][:, :, None]
    ry = rects[:, :, 1][:, :, None]
    rw = rects[:, :, 2][:, :, None]
    rh = rects[:, :, 3][:, :, None]
    y0 = ys[None, None, :] + ry                 # (K, 3, cap)
    x0 = xs[None, None, :] + rx
    y1 = y0 + rh
    x1 = x0 + rw

    def g(y, x):
        return ii_flat[img[None, None, :],
                       base[None, None, :] + y * stride[None, None, :] + x]

    area = (g(y1, x1) - g(y0, x1) - g(y1, x0) + g(y0, x0)  # (K, 3, cap)
            ).astype(jnp.float32)                  # int32 corners, exact
    feat = jnp.zeros((area.shape[0], area.shape[2]), jnp.float32)
    for r in range(rects.shape[1]):
        feat = feat + w[:, r, None] * area[:, r]
    f_norm = feat * inv_sigma[None, :] / _AREA
    votes = jnp.where(f_norm < cascade.wc_threshold[k0:k1, None],
                      cascade.left_val[k0:k1, None],
                      cascade.right_val[k0:k1, None])
    acc = jnp.zeros_like(inv_sigma)
    for k in range(k1 - k0):    # ascending-k adds, matching the fori_loop
        acc = acc + votes[k]
    return acc


def stage_sums(cascade: Cascade, cascade_static: Cascade, s0: int, s1: int,
               ii_flat: jax.Array, img: jax.Array, base: jax.Array,
               stride: jax.Array, ys: jax.Array, xs: jax.Array,
               inv_sigma: jax.Array, *, backend: str = "bulk",
               tile: tuple = ()) -> jax.Array:
    """(s1 - s0, cap) vote sums for stages ``[s0, s1)`` over a packed list.

    One call per tail *segment*: stage thresholds are applied by the
    caller between rows, so evaluating the whole run at once is exact (the
    packed list is only recompacted at segment boundaries).  ``backend``
    picks the execution strategy; all three produce bit-identical rows.
    ``tile`` is the pallas backend's lane-block shape (empty = the package
    default; the engines pass the autotuned ``plan.lane_block``) — lane
    blocking never changes the per-window arithmetic, so every tile is
    bit-identical too.  ``cascade`` carries (possibly traced) parameter
    arrays; the *static* twin provides the stage boundaries needed at
    trace time.
    """
    if backend == "pallas":
        from . import ops
        kw = {"tile": tuple(tile)} if tile else {}
        return ops.packed_stage_sums(
            cascade, cascade_static, s0, s1, ii_flat, img, base, stride,
            ys, xs, inv_sigma, **kw)
    bounds = np.asarray(cascade_static.stage_offsets)
    if backend == "bulk":
        fn = _bulk_stage_sum
    elif backend == "gather":
        fn = _gather_stage_sum
    else:
        raise ValueError(f"unknown packed-tail backend: {backend!r} "
                         f"(expected one of {BACKENDS})")
    return jnp.stack([
        fn(cascade, ii_flat, img, base, stride, ys, xs, inv_sigma,
           int(bounds[s]), int(bounds[s + 1]))
        for s in range(s0, s1)])


def tail_backends() -> tuple[str, ...]:
    """The backends that run on this process's JAX backend: Mosaic refuses
    the packed-window kernel (``packed_window.MOSAIC_REFUSAL``), so
    ``pallas`` runs only in the CPU interpreter."""
    if jax.default_backend() == "cpu":
        return BACKENDS
    return tuple(b for b in BACKENDS if b != "pallas")


def select_backend(config, n_windows: int) -> str:
    """Backend for a packed list of ``n_windows`` lanes under ``config``.

    Delegates to the plan layer's single decision function
    (:func:`repro.plan.select_backend`) — engines never call this
    directly any more; they read the per-segment/per-rung backend off
    their compiled :class:`repro.plan.CascadePlan`.  Kept here as the
    kernels-side entry point (lazy import avoids a package cycle).
    """
    from repro.plan import select_backend as _select
    return _select(config, n_windows)


def _build_workload(workload, rng):
    """Per-level SATs + sampling tables for :func:`measure_rungs`.

    ``workload`` is a list of ``(image, weight)`` — one grayscale image
    per pyramid level (the *profiled* image downscaled to each level's
    shape, when called through ``Detector.calibrated``) and that level's
    expected packed-window share (measured survivor density x window
    count).  Returns the flat multi-level SAT pair plus a sampler that
    draws a packed list of a given size with windows distributed across
    levels in proportion to the weights — the real post-compaction access
    pattern, not a single-level proxy.
    """
    from repro.core.integral import integral_images, window_inv_sigma

    sats, pairs, bases, strides, shapes = [], [], [], [], []
    base = 0
    for img, _weight in workload:
        img = jnp.asarray(np.asarray(img, np.float32))
        h, w = img.shape
        ii, pair = integral_images(img)
        sats.append(np.asarray(ii).reshape(-1))
        pairs.append(pair)
        bases.append(base)
        strides.append(w + 1)
        shapes.append((h, w))
        base += (h + 1) * (w + 1)
    ii_flat = jnp.asarray(np.concatenate(sats))[None, :]
    weights = np.asarray([max(float(wt), 0.0) for _im, wt in workload])
    if weights.sum() <= 0:
        weights = np.asarray([(h - WINDOW + 1) * (w - WINDOW + 1)
                              for h, w in shapes], np.float64)
    weights = weights / weights.sum()

    def sample(size):
        # largest-remainder split of `size` windows across levels ∝ weight;
        # the packed list stays level-sorted, like a real compaction output
        exact = weights * size
        per = np.floor(exact).astype(int)
        for i in np.argsort(-(exact - per))[:size - per.sum()]:
            per[i] += 1
        lv = np.repeat(np.arange(len(shapes)), per)
        hi_y = np.asarray([h - WINDOW + 1 for h, _w in shapes])
        hi_x = np.asarray([w - WINDOW + 1 for _h, w in shapes])
        ys = rng.integers(0, hi_y[lv]).astype(np.int32)
        xs = rng.integers(0, hi_x[lv]).astype(np.int32)
        inv = (np.concatenate([
            np.atleast_1d(np.asarray(window_inv_sigma(
                pairs[v], jnp.asarray(ys[lv == v]), jnp.asarray(xs[lv == v]),
                WINDOW)))
            for v in range(len(shapes)) if (lv == v).any()])
            if len(lv) else np.zeros(0, np.float32))
        return (jnp.zeros(len(lv), jnp.int32),
                jnp.asarray(np.asarray([bases[v] for v in lv], np.int32)),
                jnp.asarray(np.asarray([strides[v] for v in lv], np.int32)),
                jnp.asarray(ys), jnp.asarray(xs),
                jnp.asarray(inv.astype(np.float32)))

    n_windows = int(sum((h - WINDOW + 1) * (w - WINDOW + 1)
                        for h, w in shapes))
    return ii_flat, sample, n_windows


def measure_rungs(cascade: Cascade, *, sizes: tuple = DEFAULT_RUNG_SIZES,
                  repeats: int = 3, inner: int = 10, seed: int = 0,
                  workload: list | None = None) -> dict:
    """Race the packed-tail backends at capacity-ladder sizes.

    Builds a representative packed workload and times each backend
    evaluating the *full* cascade per size (best-of-``repeats`` over
    ``inner`` warm iterations), returning::

        {"sizes": [...], "n_windows": int, "levels": int,
         "ms": {backend: [...]},
         "rungs": ((max_windows, winner), ...), "crossover": int}

    ``workload`` is an optional list of ``(level_image, weight)`` pairs —
    the profiled image's real pyramid levels with their measured
    packed-window shares (``Detector.calibrated(tune_tail=True)`` passes
    this off the plan's level layout), so the race runs the true
    multi-level gather pattern of a skewed pyramid.  Without it a
    synthetic single 160x160 level with uniform windows is used.

    ``n_windows`` is the workload's dense window count, so
    ``size / n_windows`` is the survivor *density* each rung corresponds
    to (the x-axis of the crossover sweep in ``bench_detector``).

    ``crossover`` is the smallest rung won by the Pallas kernel (-1 if it
    never wins — a legitimate outcome on hardware where gathers are cheap).
    Only :func:`tail_backends` race, so on TPU ``pallas`` is left out.
    """
    rng = np.random.default_rng(seed)
    if workload is None:
        workload = [(rng.integers(0, 255, (160, 160)).astype(np.float32),
                     1.0)]
    ii_flat, sample, n_windows = _build_workload(workload, rng)
    n_stages = cascade.n_stages
    backends = tail_backends()
    ms: dict[str, list] = {b: [] for b in backends}

    for size in sizes:
        imgi, base, stride, ys, xs, inv = sample(size)
        for bk in backends:
            # repro: ignore[JIT_CACHE] bench harness: one fresh jitted fn per (size, backend) point is the measurement unit; compile cost is excluded by the warm-up call below
            fn = jax.jit(lambda c, iif, iv, _bk=bk: stage_sums(
                c, cascade, 0, n_stages, iif, imgi, base, stride, ys, xs,
                iv, backend=_bk))
            jax.block_until_ready(fn(cascade, ii_flat, inv))   # compile
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                for _ in range(inner):
                    out = fn(cascade, ii_flat, inv)
                jax.block_until_ready(out)
                best = min(best, (time.perf_counter() - t0) / inner)
            ms[bk].append(best * 1e3)

    rungs = tuple(
        (size, min(backends, key=lambda b: ms[b][i]))
        for i, size in enumerate(sizes))
    crossover = next((size for size, bk in rungs if bk == "pallas"), -1)
    return {"sizes": list(sizes), "n_windows": n_windows,
            "levels": len(workload), "ms": ms,
            "rungs": rungs, "crossover": crossover}
