"""Block-shape autotuner for the Pallas kernels.

This module is the single home of the repo's tile / lane-block literals:
``DEFAULT_TILE`` (the (8, 128) f32 VPU tile every kernel defaults to) and
the candidate tables the tuner races.  The analysis ``LANE_BLOCK`` rule
permits the literals *here only* — everywhere else a tile shape must be
imported from this table or read off the compiled plan, so a block shape
is always a tuned, persisted decision rather than a scattered constant
(Catalan et al.'s point that block-size configuration is as
architecture-dependent as the kernel itself).

Naming note: :mod:`repro.scheduling.autotune` is the paper's
step/scaleFactor *accuracy* sweep (paper section 7.3, Fig. 20) and is
unrelated; kernel block-shape tuning lives here, next to the kernels it
tunes.

Two racers, both run on the calibrated workload (the profiled image at
every pyramid level, as built by ``Detector.calibrated``):

- :func:`measure_head` — the fused Haar-head megakernel
  (:mod:`repro.kernels.fused_head`) vs the split three-dispatch path,
  per pyramid level and over candidate head tiles.  Produces the
  ``head_rungs`` crossover ladder and the winning ``head_tile``.
- :func:`measure_lane_block` — packed-tail lane-block shapes at the
  calibrated packed-list size.  Produces the winning ``lane_block``.

``Detector.calibrated(tune_head=True)`` persists the winners in
``EngineConfig.head_rungs`` / ``head_tile`` / ``lane_block`` and in
``cal_profile["head_tiles"]`` / ``cal_profile["lane_block"]`` next to
``tail_rungs``; :mod:`repro.plan.compiler` is the single consumer.  On
TPU hardware, re-measuring is a re-run of ``calibrated(tune_tail=True,
tune_head=True)``, not a rewrite.
"""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["DEFAULT_TILE", "HEAD_TILE_CANDIDATES", "LANE_BLOCK_CANDIDATES",
           "measure_head", "measure_lane_block"]

# the native (sublane, lane) f32 VPU tile — every kernel's default block
DEFAULT_TILE = (8, 128)

# head-tile candidates raced by measure_head: taller blocks amortize more
# per-grid-step overhead; wider blocks trade VMEM for fewer column steps
HEAD_TILE_CANDIDATES = ((8, 128), (16, 128), (8, 256))

# lane-block candidates for the packed tail's (rows, lanes) window blocks
LANE_BLOCK_CANDIDATES = ((8, 128), (16, 128), (8, 256))


def _best_ms(fn, args, repeats: int, inner: int) -> float:
    """Best-of-``repeats`` mean wall time (ms) over ``inner`` warm calls."""
    jax.block_until_ready(fn(*args))         # compile outside the clock
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best * 1e3


def _tile_label(tile) -> str:
    return f"{tile[0]}x{tile[1]}"


def measure_head(cascade, workload, *, n_dense: int,
                 candidates=HEAD_TILE_CANDIDATES, repeats: int = 2,
                 inner: int = 3) -> dict:
    """Race the fused head megakernel against the split three-dispatch path.

    ``workload`` is the calibrated ``(level_image, weight)`` list (the
    profiled image downscaled to every pyramid level; the weights are the
    tail's — the dense head always sweeps the full grid, so levels are
    compared by their own window counts).  ``n_dense`` is the plan's
    dense-prefix stage count.  Per level, times the split path (jnp SAT +
    1/sigma, one haar_stage dispatch per dense stage) and the fused
    megakernel at each candidate tile.  Returns::

        {"levels": [(h, w, n_windows), ...],
         "ms": {"split": [...], "fused": [...]},     # fused = winner tile
         "tile_ms": {"8x128": [...], ...},           # fused, per candidate
         "head_tiles": (ty, tx),                     # total-time winner
         "rungs": ((n_windows, mode), ...),          # ascending by windows
         "crossover": int}                           # smallest fused win, -1

    ``rungs`` is the value persisted as ``EngineConfig.head_rungs``; the
    plan compiler (:func:`repro.plan.compiler.select_head_mode`) walks it
    exactly like the tail's crossover ladder.
    """
    from repro.core.cascade import WINDOW
    from repro.core.integral import integral_images, window_inv_sigma
    from . import ops

    n_dense = min(int(n_dense), cascade.n_stages)
    assert n_dense >= 1, "measure_head needs at least one dense stage"
    candidates = tuple(tuple(c) for c in candidates)
    levels: list[tuple[int, int, int]] = []
    split_ms: list[float] = []
    tile_ms: dict[str, list[float]] = {_tile_label(c): [] for c in candidates}

    for img, _weight in workload:
        img = jnp.asarray(np.asarray(img, np.float32))
        h, w = img.shape
        ny, nx = h - WINDOW + 1, w - WINDOW + 1
        levels.append((h, w, ny * nx))

        def split_head(c, im, ny=ny, nx=nx):
            ii, pair = integral_images(im)
            inv = window_inv_sigma(pair, jnp.arange(ny)[:, None],
                                   jnp.arange(nx)[None, :], WINDOW)
            sums = [ops.dense_stage_sums(c, cascade, s, ii, inv)
                    for s in range(n_dense)]
            return ii, inv, sums

        # repro: ignore[JIT_CACHE] tuner harness: one fresh jitted fn per measured (level, variant) point is the measurement unit; compile cost is excluded by the warm-up call in _best_ms
        split_ms.append(_best_ms(jax.jit(split_head), (cascade, img),
                                 repeats, inner))
        for cand in candidates:
            def fused_head(c, im, _t=cand):
                return ops.fused_head(c, cascade, 0, n_dense, im, tile=_t)

            # repro: ignore[JIT_CACHE] tuner harness: one fresh jitted fn per measured (level, tile) point is the measurement unit; compile cost is excluded by the warm-up call in _best_ms
            fn = jax.jit(fused_head)
            tile_ms[_tile_label(cand)].append(
                _best_ms(fn, (cascade, img), repeats, inner))

    totals = [sum(tile_ms[_tile_label(c)]) for c in candidates]
    winner = candidates[int(np.argmin(totals))]
    fused_ms = list(tile_ms[_tile_label(winner)])

    order = np.argsort([nwin for (_h, _w, nwin) in levels], kind="stable")
    rungs = tuple(
        (levels[i][2],
         "fused" if fused_ms[i] <= split_ms[i] else "split")
        for i in order)
    crossover = next((nw for nw, mode in rungs if mode == "fused"), -1)
    return {"levels": levels,
            "ms": {"split": split_ms, "fused": fused_ms},
            "tile_ms": tile_ms, "head_tiles": winner,
            "rungs": rungs, "crossover": crossover}


def measure_lane_block(cascade, workload=None, *, size: int = 2048,
                       candidates=LANE_BLOCK_CANDIDATES, repeats: int = 3,
                       inner: int = 5, seed: int = 0) -> dict:
    """Race packed-tail lane-block shapes at one packed-list size.

    Reuses :func:`repro.kernels.packed_tail._build_workload`'s real
    multi-level sampler, then times the Pallas packed backend evaluating
    the full cascade at each candidate ``tile``.  ``size`` should be the
    calibrated tail crossover (the smallest packed-list size routed to
    the kernel), so the winner is tuned where the kernel actually runs.
    The kernel runs only where ``packed_tail.tail_backends()`` lists it
    (not on TPU).  Returns ``{"size", "n_windows", "candidates", "ms",
    "lane_block"}``.
    """
    from . import packed_tail

    rng = np.random.default_rng(seed)
    if workload is None:
        workload = [(rng.integers(0, 255, (160, 160)).astype(np.float32),
                     1.0)]
    ii_flat, sample, n_windows = packed_tail._build_workload(workload, rng)
    n_stages = cascade.n_stages
    candidates = tuple(tuple(c) for c in candidates)
    imgi, base, stride, ys, xs, inv = sample(int(size))
    ms: list[float] = []
    for cand in candidates:
        # repro: ignore[JIT_CACHE] tuner harness: one fresh jitted fn per candidate lane block is the measurement unit; compile cost is excluded by the warm-up call in _best_ms
        fn = jax.jit(lambda c, iif, iv, _t=cand: packed_tail.stage_sums(
            c, cascade, 0, n_stages, iif, imgi, base, stride, ys, xs, iv,
            backend="pallas", tile=_t))
        ms.append(_best_ms(fn, (cascade, ii_flat, inv), repeats, inner))
    winner = candidates[int(np.argmin(ms))]
    return {"size": int(size), "n_windows": int(n_windows),
            "candidates": [tuple(c) for c in candidates], "ms": ms,
            "lane_block": winner}
