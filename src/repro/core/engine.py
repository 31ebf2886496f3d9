"""Detection engines (paper §6–§7.1, re-architected for SIMD/TPU).

Two execution strategies over the same cascade semantics:

- ``mode="dense"`` — the paper-faithful parallel baseline: *delayed
  rejection* (§7.1).  Every stage is evaluated for every window; the
  inter-stage dependency is broken exactly the way the paper describes
  ("delaying the rejection of a region"), which maximizes parallelism at
  the cost of redundant compute.  On a CPU this is what
  ``#pragma omp for schedule(static)`` over windows gives you once tasks
  are made uniform.

- ``mode="wave"`` — the TPU-native optimization: stages are grouped into
  *segments*; each segment is evaluated as a dense SIMD wave over the
  currently-live windows, then survivors are **compacted** (static-capacity
  ``nonzero``) so the next wave runs at high lane occupancy.  This replaces
  OmpSs per-core task stealing: dynamic irregularity is converted into a
  static pipeline of shrinking dense batches.  Segment boundaries and
  capacities are profile-guided (see ``calibrate_capacities``), mirroring
  the paper's measured per-stage rejection profile.

Left unset, ``EngineConfig.mode`` follows the platform
(:func:`repro.kernels.platform.mode_by_default`): ``dense`` on TPU, where
the compacted tail's XLA gathers are serialized, ``wave`` elsewhere.

The first (densest) waves can run through the Pallas tile kernel
(``repro.kernels.ops.dense_stage_sums``) — on the single-image path *and*
on the packed batched head, which routes per-level dense waves through the
batched wrapper ``dense_stage_sums_batch`` (one dispatch per (stage,
level) over the whole stack); later segments run the compacted window
list through the shared packed-tail evaluator
(``repro.kernels.packed_tail``), whose backend — fori-loop gather, bulk
gather, or the blocked packed-window Pallas kernel — is chosen per
capacity rung by the measured crossover ladder
(``EngineConfig.tail_rungs``, see ``Detector.calibrated``).  All backends
and the dense kernels are verified bit-identical on the test corpus (in
the Pallas interpreter on CPU; on TPU the kernels compile through
Mosaic).  This dense/packed/gather spectrum is the SIMD
re-expression of the paper's "balance between parallelism and optimal
computational workload".

Batching (serving scale)
------------------------
``Detector.detect_batch`` runs many images at once.  Its default
``strategy="packed"`` compiles one program per (bucket shape, batch size)
that runs the dense waves per level over the whole stack and then compacts
survivors from *every image and pyramid level* into one shared window list
for the tail stages — amortizing the per-(image, level) static capacity
floor across the flush (see ``_build_batch_fn``); after
``Detector.calibrated`` this is several times faster than the
one-at-a-time loop.  ``strategy="vmap"`` instead ``vmap``s ``level_fn``
over a leading batch axis: one dispatch per pyramid level instead of one
per (image, level), batched ``LevelResult``s, and per-image overflow
accounting.  Mixed resolutions
are handled by *shape bucketing*: with ``EngineConfig.pad_multiple > 0``
every image is zero-padded up to the next multiple on each side, so a
traffic mix of arbitrary shapes compiles only a handful of bucket
programs.  Windows whose receptive field would sample padded pixels are
masked out via a per-image dynamic ``limits`` argument, so padding never
introduces detections.  The single-image ``detect`` uses the identical
padded program, which makes ``detect_batch`` bit-identical per image to
sequential ``detect`` under any bucket policy.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from .cascade import Cascade, WINDOW
from .integral import integral_images, window_inv_sigma_grid
from .features import run_sums_grid, stage_sum_windows
from .pyramid import downscale_nearest, downscale_indices
from . import nms
from repro import obs
from repro.kernels import packed_tail
from repro.kernels.autotune import DEFAULT_TILE
from repro.kernels.platform import kernels_by_default, mode_by_default
import repro.plan as planlib

__all__ = ["EngineConfig", "LevelResult", "BatchResult", "Detector",
           "CapacityOverflow", "calibrate_capacities"]


class CapacityOverflow(RuntimeError):
    """More windows survived a compaction than its static capacity holds,
    so the result would drop some: the one engine error that depends on
    the image content rather than on the program."""


class EngineConfig(NamedTuple):
    step: int = 1                  # window stride (paper §7.3 'step')
    scale_factor: float = 1.2      # pyramid ratio (paper §7.3 'scaleFactor')
    mode: str | None = None        # 'dense' | 'wave'; None = the
    #                                platform's choice (dense on TPU only)
    dense_segments: tuple = (1, 2)  # stage counts of dense (full-grid) waves
    compact_every: int = 3         # stages per segment in the compacted tail
    capacity_fracs: tuple = ()     # per-compaction survivor capacity as a
    #                                fraction of the level's window count;
    #                                () = auto (2 * 0.5^(k+1), floor 0.02)
    use_pallas: bool | None = None  # dense waves via Pallas kernels; None =
    #                                the platform's choice (on for TPU only),
    #                                False = the jnp oracle path
    min_neighbors: int = 3
    pad_multiple: int = 0          # shape-bucket rounding: images are padded
    #                                up to the next multiple per side so mixed
    #                                resolutions share a few compiled bucket
    #                                programs (0 = exact shapes, no padding)
    batch_capacity_fracs: tuple = ()  # per-compaction survivor fracs of the
    #                                batched engine's *shared* window list,
    #                                as fractions of the whole batch's window
    #                                count; () = fall back to capacity_fracs,
    #                                else the conservative auto schedule
    tail_backend: str = "auto"     # packed-tail evaluator: 'gather' | 'bulk'
    #                                | 'pallas' forces one backend; 'auto'
    #                                walks the calibrated tail_rungs ladder
    #                                (empty ladder = 'bulk')
    tail_rungs: tuple = ()         # measured kernel-vs-gather crossover
    #                                ladder ((max_windows, backend), ...)
    #                                ascending, persisted by
    #                                Detector.calibrated(tune_tail=True) so
    #                                batch and serving inherit one decision
    head_mode: str = "auto"        # dense-head execution: 'fused' one-dispatch
    #                                megakernel | 'split' three-dispatch path
    #                                forces one; 'auto' walks the calibrated
    #                                head_rungs ladder (empty ladder = fused;
    #                                non-Pallas / strided configs always split)
    head_rungs: tuple = ()         # measured fused-vs-split crossover ladder
    #                                ((max_windows, mode), ...) ascending,
    #                                persisted by calibrated(tune_head=True)
    head_tile: tuple = ()          # autotuned dense-head tile shape (ty, tx);
    #                                () = the package default — winners from
    #                                kernels.autotune.measure_head persist here
    lane_block: tuple = ()         # autotuned packed-tail lane-block shape;
    #                                () = default — winners from
    #                                kernels.autotune.measure_lane_block


class LevelResult(NamedTuple):
    ys: jax.Array            # (cap,) int32 window origins (-1 = invalid)
    xs: jax.Array            # (cap,) int32
    valid: jax.Array         # (cap,) bool
    alive_counts: jax.Array  # (n_stages,) int32 — survivors after each stage
    overflow: jax.Array      # () bool — capacity exceeded (would drop windows)


class BatchResult(NamedTuple):
    """Survivors of a whole (batch x pyramid) packed detection pass."""
    img: jax.Array           # (cap,) int32 batch index (-1 = invalid lane)
    lvl: jax.Array           # (cap,) int32 pyramid-level index
    ys: jax.Array            # (cap,) int32 window origin at that level
    xs: jax.Array            # (cap,) int32
    valid: jax.Array         # (cap,) bool
    alive_counts: jax.Array  # (n_stages, B) int32 — per-image survivors after
    #                          each stage, summed over pyramid levels
    overflow: jax.Array      # () bool — shared capacity exceeded
    head_work: jax.Array     # (B, 2) int32 — per image, weak-classifier
    #                          evaluations of (ty, tx) tiles the dense head
    #                          ran, and those of the dense head with no exit


def calibrate_capacities(alive_counts: np.ndarray, n_windows: int,
                         safety: float = 2.0) -> tuple:
    """Profile-guided capacity fractions from measured per-stage survivor
    counts (run the engine once with generous capacities, feed back)."""
    fr = np.asarray(alive_counts, np.float64) / max(n_windows, 1)
    return tuple(float(min(1.0, f * safety + 1e-3)) for f in fr)


def _window_limits(h_valid, w_valid, level_h: int, level_w: int,
                   pad_h: int, pad_w: int):
    """Delegates to the plan layer's single definition of window-limit
    arithmetic (call-time lookup keeps the circular package import lazy)."""
    return planlib.window_limits(h_valid, w_valid, level_h, level_w,
                                 pad_h, pad_w)


class Detector:
    """Multi-scale face detector over one cascade.

    Per-pyramid-level jitted programs are cached by image shape; the host
    loop walks the (static-shape) pyramid plan, mirroring the reference C
    code's ``ScaleImage_Invoker`` structure.
    """

    def __init__(self, cascade: Cascade, config: EngineConfig = EngineConfig()):
        if config.use_pallas is None:
            config = config._replace(use_pallas=kernels_by_default())
        if config.mode is None:
            config = config._replace(mode=mode_by_default())
        self.cascade = cascade
        self.config = config
        self.stage_bounds = tuple(int(o) for o in np.asarray(cascade.stage_offsets))
        self.n_stages = cascade.n_stages
        planlib.validate_config(self.n_stages, config)
        self.cal_profile: dict = {}      # set by calibrated() on its result
        self.program_builds = 0          # executor builds (plan-cache probe)
        self._raw_level_fns: dict = {}   # level-plan key -> unjitted level fn
        self._level_fns: dict = {}       # level-plan key -> jitted level fn
        self._vmap_level_fns: dict = {}  # (key, B) -> jit(vmap(level fn))
        self._batch_fns: dict = {}       # batch-plan key -> packed batch fn
        self._batch_heads: dict = {}     # batch-plan key -> unjitted head fn
        self._batch_tails: dict = {}     # batch-plan key -> unjitted tail fn

    # ---------------------------------------------------------------- plan
    def _segments(self) -> list[tuple[int, int, bool]]:
        """[(s0, s1, dense?)] covering all stages in order (the plan
        layer's segmentation; kept as a method for callers/benchmarks)."""
        return [tuple(s) for s in planlib.segment_spans(self.n_stages,
                                                        self.config)]

    def level_plan(self, h: int, w: int) -> "planlib.LevelWavePlan":
        """Compiled plan of the single-image wave program at one level
        shape (cached by the plan compiler)."""
        return planlib.compile_level_plan(self.config, self.n_stages, h, w)

    def batch_plan(self, hp: int, wp: int,
                   batch: int = 1) -> "planlib.CascadePlan":
        """Compiled plan of the packed batched program for one (bucket,
        batch size) (cached by the plan compiler)."""
        return planlib.compile_plan(self.config, self.n_stages, hp, wp,
                                    batch=batch)

    # ---------------------------------------------------------------- build
    def _build_level_fn(self, lp: "planlib.LevelWavePlan"):
        """Thin executor over a :class:`repro.plan.LevelWavePlan`: all
        geometry, segmentation, and capacities are read off the plan."""
        cfg = self.config
        step = lp.step
        ny, nx = lp.ny, lp.nx
        segs = lp.segments
        self.program_builds += 1
        bounds = self.stage_bounds
        cascade_static = self.cascade  # static feature geometry for Pallas

        n_dense_lp = sum(seg.s1 - seg.s0 for seg in segs if seg.dense)
        fused = lp.head_mode == "fused" and n_dense_lp > 0
        split_kernels = cfg.use_pallas and step == 1 and not fused
        head_tile = lp.head_tile
        if cfg.use_pallas or fused:
            from repro.kernels import ops as kops
            if not head_tile:
                from repro.kernels.autotune import DEFAULT_TILE as head_tile

        def level_fn(cascade: Cascade, img: jax.Array,
                     limits: jax.Array) -> LevelResult:
            if fused:
                # whole dense head — SAT and 1/sigma (XLA), then the sums
                # of every dense stage its tiles reach in one megakernel
                # dispatch (the split path's bits wherever a tile ran,
                # -inf where it exited, so the chain below decides as the
                # split path does; the plan chose per measured crossover)
                ii, inv_sigma_grid, dsums = kops.fused_head(
                    cascade, cascade_static, 0, n_dense_lp, img,
                    tile=head_tile)
            else:
                ii, ii_pair = integral_images(img)
            gy = jnp.arange(ny, dtype=jnp.int32) * step
            gx = jnp.arange(nx, dtype=jnp.int32) * step
            ys = jnp.repeat(gy, nx)
            xs = jnp.tile(gx, ny)
            if not fused:
                inv_sigma_grid = window_inv_sigma_grid(
                    ii_pair, ny, nx, step, WINDOW)           # (ny, nx)
                if not split_kernels:
                    dsums = run_sums_grid(cascade, ii, inv_sigma_grid,
                                          step, 0, n_dense_lp)
            inv_sigma = inv_sigma_grid.reshape(-1)

            # dense-grid liveness; ``limits`` masks windows whose receptive
            # field would sample padded pixels (permissive when unpadded)
            alive = (ys <= limits[0]) & (xs <= limits[1])
            counts: list[jax.Array] = []
            overflow = jnp.asarray(False)

            # state of the compacted list (after first compaction)
            compacted = False
            cur_ys = cur_xs = cur_inv = cur_valid = None

            for seg in segs:
                s0, s1, dense = seg.s0, seg.s1, seg.dense
                if dense:
                    for s in range(s0, s1):
                        if split_kernels:
                            ss = kops.dense_stage_sums(
                                cascade, cascade_static, s, ii, inv_sigma_grid,
                                tile=head_tile).reshape(-1)
                        else:
                            ss = dsums[s].reshape(-1)
                        alive = alive & (ss >= cascade.stage_threshold[s])
                        counts.append(alive.sum())
                else:
                    # (re-)compact from whichever list is current
                    if not compacted:
                        src_valid, src_ys, src_xs, src_inv = (
                            alive, ys, xs, inv_sigma)
                    else:
                        src_valid, src_ys, src_xs, src_inv = (
                            cur_valid, cur_ys, cur_xs, cur_inv)
                    cap = seg.capacity
                    overflow = overflow | (src_valid.sum() > cap)
                    idx = jnp.nonzero(src_valid, size=cap, fill_value=-1)[0]
                    sel = jnp.maximum(idx, 0)
                    cur_ys = jnp.take(src_ys, sel)
                    cur_xs = jnp.take(src_xs, sel)
                    cur_inv = jnp.take(src_inv, sel)
                    cur_valid = idx >= 0
                    compacted = True
                    for s in range(s0, s1):
                        k0, k1 = bounds[s], bounds[s + 1]
                        ss = stage_sum_windows(cascade, ii, cur_ys, cur_xs,
                                               cur_inv, k0, k1)
                        cur_valid = cur_valid & (ss >= cascade.stage_threshold[s])
                        counts.append(cur_valid.sum())

            if not compacted:   # dense mode: single final compaction
                cap = lp.capacities[0]
                overflow = alive.sum() > cap
                idx = jnp.nonzero(alive, size=cap, fill_value=-1)[0]
                sel = jnp.maximum(idx, 0)
                cur_ys = jnp.take(ys, sel)
                cur_xs = jnp.take(xs, sel)
                cur_valid = idx >= 0

            out_ys = jnp.where(cur_valid, cur_ys, -1)
            out_xs = jnp.where(cur_valid, cur_xs, -1)
            return LevelResult(out_ys, out_xs, cur_valid,
                               jnp.stack(counts).astype(jnp.int32), overflow)

        return level_fn

    def _raw_level_fn(self, h: int, w: int):
        lp = self.level_plan(h, w)
        if lp.key not in self._raw_level_fns:
            self._raw_level_fns[lp.key] = self._build_level_fn(lp)
        return self._raw_level_fns[lp.key]

    def _level_fn(self, h: int, w: int):
        key = self.level_plan(h, w).key
        if key not in self._level_fns:
            self._level_fns[key] = jax.jit(self._raw_level_fn(h, w))
        return self._level_fns[key]

    def _vmap_level_fn(self, h: int, w: int, batch: int):
        """jit(vmap(level_fn)) — batch variants share the per-plan builder."""
        key = (self.level_plan(h, w).key, batch)
        if key not in self._vmap_level_fns:
            self._vmap_level_fns[key] = jax.jit(
                jax.vmap(self._raw_level_fn(h, w), in_axes=(None, 0, 0)))
        return self._vmap_level_fns[key]

    # ------------------------------------------------------------ buckets
    def _bucket_hw(self, h: int, w: int) -> tuple[int, int]:
        """Shape bucket for an (h, w) image under the pad policy."""
        m = self.config.pad_multiple
        if m <= 0:
            return h, w
        hp = max(((h + m - 1) // m) * m, WINDOW)
        wp = max(((w + m - 1) // m) * m, WINDOW)
        return hp, wp

    def _padded_plan(self, h: int, w: int):
        hp, wp = self._bucket_hw(h, w)
        return hp, wp, self.batch_plan(hp, wp).levels

    @staticmethod
    def _decode_rects(ys: np.ndarray, xs: np.ndarray,
                      scales: np.ndarray) -> np.ndarray:
        """Window origins (level coords) -> (N, 4) int32 [x, y, w, h] rects
        in image coords (round-half-even, matching ``round``)."""
        ys = np.asarray(ys, np.float64)
        xs = np.asarray(xs, np.float64)
        scales = np.broadcast_to(np.asarray(scales, np.float64), ys.shape)
        w = np.rint(WINDOW * scales)
        return np.stack([np.rint(xs * scales), np.rint(ys * scales), w, w],
                        axis=1).astype(np.int32).reshape(-1, 4)

    # ---------------------------------------------------------------- public
    def detect_raw(self, image) -> list[tuple[LevelResult, float]]:
        """Per-level raw results (device arrays) + level scales."""
        image = np.asarray(image, np.float32)
        h, w = image.shape
        hp, wp, plan = self._padded_plan(h, w)
        if (hp, wp) != (h, w):
            image = np.pad(image, ((0, hp - h), (0, wp - w)))
        image = jnp.asarray(image)
        out = []
        for lv in plan:
            img_s = downscale_nearest(image, lv.height, lv.width)
            limits = jnp.asarray(
                _window_limits(h, w, lv.height, lv.width, hp, wp), jnp.int32)
            res = self._level_fn(lv.height, lv.width)(
                self.cascade, img_s, limits)
            out.append((res, lv.scale))
        return out

    def detect(self, image, group: bool = True) -> np.ndarray:
        """Detect faces; returns (M, 4) int32 [x, y, w, h] in image coords."""
        rects = []
        for res, scale in self.detect_raw(image):
            if bool(np.asarray(res.overflow)):
                raise CapacityOverflow(
                    "wave-engine capacity overflow; raise capacity_fracs "
                    "(see calibrate_capacities)")
            val = np.asarray(res.valid)
            rects.append(self._decode_rects(np.asarray(res.ys)[val],
                                            np.asarray(res.xs)[val],
                                            scale))
        rects = (np.concatenate(rects, axis=0) if rects
                 else np.zeros((0, 4), np.int32))
        if not group:
            return rects
        return nms.group_rectangles(rects, self.config.min_neighbors)

    # ---------------------------------------------------------------- batch
    def _dense_prefix(self) -> int:
        """Number of leading stages run as dense (full-grid) waves."""
        return sum(s1 - s0 for (s0, s1, dense) in self._segments() if dense)

    def _build_batch_fn(self, plan: "planlib.CascadePlan"):
        """One jitted program per :class:`repro.plan.CascadePlan` (bucket
        shape, batch size): per-level dense waves over the whole stack,
        then *shared* compactions — survivors from every (image, level)
        are packed into one window list for the tail stages, recompacted
        per segment exactly like the single-image wave engine.  This is
        the paper's lane-occupancy argument applied across the batch: the
        per-(image, level) static capacity floor is paid once per flush
        instead of B*L times.  All geometry, slot/SAT layout, capacities,
        and per-segment tail backends are read off the plan."""
        cfg = self.config
        step = plan.step
        batch = plan.batch
        hp, wp = plan.hp, plan.wp
        n_dense = plan.dense_prefix
        bounds = self.stage_bounds
        n_stages = self.n_stages
        cascade_static = self.cascade  # static feature geometry for Pallas
        use_pallas = cfg.use_pallas and step == 1
        self.program_builds += 1
        head_tile = plan.head_tile or DEFAULT_TILE
        lane_block = plan.lane_block
        if use_pallas:
            from repro.kernels import ops as kops
        # weak classifiers per dense stage: the head's work per tile-stage
        n_weak = np.diff(bounds)[:n_dense]
        ty, tx = head_tile

        layout = plan.layout
        lvl_of_slot = jnp.asarray(layout.lvl_of_slot)
        y_of_slot = jnp.asarray(layout.y_of_slot)
        x_of_slot = jnp.asarray(layout.x_of_slot)
        sat_base_of_lvl = jnp.asarray(layout.sat_base_of_lvl)
        sat_stride_of_lvl = jnp.asarray(layout.sat_stride_of_lvl)
        n_slots = plan.n_slots
        cap0 = plan.capacities[0]
        tail_segs = plan.tail_segments

        def head_fn(cascade: Cascade, stack: jax.Array,
                    valid_hw: jax.Array):
            # stack: (B, hp, wp) f32; valid_hw: (B, 2) int32 true shapes
            counts = jnp.zeros((n_stages, batch), jnp.int32)
            work = jnp.zeros((batch,), jnp.int32)
            dense_work = 0
            # per-level SATs, flattened per level and concatenated, feed the
            # packed tail's gathers; dense mode (no tail) never builds them
            sat_parts: list = []
            alive_parts, inv_parts = [], []
            for li, lp in enumerate(plan.levels):
                ys_idx = downscale_indices(hp, lp.height)
                xs_idx = downscale_indices(wp, lp.width)
                img_l = stack[:, ys_idx[:, None], xs_idx[None, :]]
                fused_l = plan.head_modes[li] == "fused" and n_dense > 0

                def head(img, ny=lp.ny, nx=lp.nx):
                    ii, ii_pair = integral_images(img)
                    inv = window_inv_sigma_grid(ii_pair, ny, nx, step,
                                                WINDOW)
                    return ii, inv                            # (ny, nx) grid

                dense_l = (-(-lp.ny // ty) * -(-lp.nx // tx)
                           * int(n_weak.sum()))
                dense_work += dense_l
                if fused_l:
                    # SAT and 1/sigma, then the sums of every dense stage
                    # its tiles reach for the whole stack in one batched
                    # megakernel dispatch (the split path's bits wherever
                    # a tile ran; the plan chose per level from the
                    # measured fused-vs-split crossover)
                    ii_l, inv_grid_l, sums_l = kops.fused_head_batch(
                        cascade, cascade_static, 0, n_dense, img_l,
                        tile=head_tile)
                    # a tile's origin reads -inf at each stage it skipped
                    ran = sums_l[:, :, ::ty, ::tx] > -jnp.inf
                    work = work + (ran.sum(axis=(2, 3)) * jnp.asarray(
                        n_weak, jnp.int32)).sum(axis=1)
                else:
                    work = work + dense_l
                    ii_l, inv_grid_l = jax.vmap(head)(img_l)  # (B,h+1,w+1),(B,ny,nx)
                    if not use_pallas:
                        sums_l = jax.vmap(
                            lambda ii_b, inv_b: run_sums_grid(
                                cascade, ii_b, inv_b, step, 0, n_dense)
                        )(ii_l, inv_grid_l)            # (B, n_dense, ny, nx)
                inv_l = inv_grid_l.reshape(batch, -1)
                if tail_segs:
                    sat_parts.append(ii_l.reshape(batch, -1))
                sl = slice(lp.slot_offset, lp.slot_offset + lp.n_windows)
                ys_w = jnp.asarray(layout.y_of_slot[sl])
                xs_w = jnp.asarray(layout.x_of_slot[sl])
                y_lim, x_lim = _window_limits(
                    valid_hw[:, 0], valid_hw[:, 1], lp.height, lp.width,
                    hp, wp)                                   # (B,), (B,)
                alive_l = ((ys_w[None, :] <= y_lim[:, None])
                           & (xs_w[None, :] <= x_lim[:, None]))  # (B, n)
                for s in range(n_dense):
                    if use_pallas and not fused_l:
                        # dense waves through the Pallas tile kernel, one
                        # dispatch per (stage, level) over the whole stack —
                        # same kernel the single-image level_fn runs
                        ss = kops.dense_stage_sums_batch(
                            cascade, cascade_static, s, ii_l, inv_grid_l,
                            tile=head_tile).reshape(batch, -1)
                    else:
                        ss = sums_l[:, s].reshape(batch, -1)  # (B, n)
                    alive_l = alive_l & (ss >= cascade.stage_threshold[s])
                    counts = counts.at[s].add(
                        alive_l.sum(axis=1).astype(jnp.int32))
                alive_parts.append(alive_l)
                inv_parts.append(inv_l)

            alive_flat = jnp.concatenate(alive_parts, axis=1).reshape(-1)
            inv_flat = jnp.concatenate(inv_parts, axis=1).reshape(-1)
            ii_flat = (jnp.concatenate(sat_parts, axis=1) if tail_segs
                       else None)                         # (B, sum sat sizes)
            head_work = jnp.stack(
                [work, jnp.full((batch,), dense_work, jnp.int32)], axis=1)
            return alive_flat, inv_flat, ii_flat, counts, head_work

        def tail_fn(cascade: Cascade, alive_flat: jax.Array,
                    inv_flat: jax.Array, ii_flat, counts,
                    head_work: jax.Array) -> BatchResult:
            # ---- shared compactions across the whole (batch x pyramid):
            # survivors from every image and level share one window list,
            # recompacted per tail segment like the single-image wave engine
            overflow = alive_flat.sum() > cap0
            idx = jnp.nonzero(alive_flat, size=cap0, fill_value=-1)[0]
            sel = jnp.maximum(idx, 0)
            valid = idx >= 0
            b_sel = sel // n_slots
            slot = sel % n_slots
            lvl_sel = jnp.take(lvl_of_slot, slot)
            y_sel = jnp.take(y_of_slot, slot)
            x_sel = jnp.take(x_of_slot, slot)
            inv_sel = jnp.take(inv_flat, sel)

            for ki, seg in enumerate(tail_segs):
                s0, s1, seg_cap = seg.s0, seg.s1, seg.capacity
                if ki > 0:  # recompact the shrinking shared list
                    overflow = overflow | (valid.sum() > seg_cap)
                    idx = jnp.nonzero(valid, size=seg_cap, fill_value=-1)[0]
                    sel = jnp.maximum(idx, 0)
                    b_sel = jnp.take(b_sel, sel)
                    lvl_sel = jnp.take(lvl_sel, sel)
                    y_sel = jnp.take(y_sel, sel)
                    x_sel = jnp.take(x_sel, sel)
                    inv_sel = jnp.take(inv_sel, sel)
                    valid = idx >= 0
                base_sel = jnp.take(sat_base_of_lvl, lvl_sel)
                stride_sel = jnp.take(sat_stride_of_lvl, lvl_sel)
                # whole segment in one evaluator call: the backend is the
                # plan's per-segment decision off the calibrated crossover
                # ladder (stage thresholds still gate survivors below)
                ss_run = packed_tail.stage_sums(
                    cascade, cascade_static, s0, s1, ii_flat, b_sel,
                    base_sel, stride_sel, y_sel, x_sel, inv_sel,
                    backend=seg.backend, tile=lane_block)
                for j, s in enumerate(range(s0, s1)):
                    valid = valid & (ss_run[j] >= cascade.stage_threshold[s])
                    per_img = jnp.zeros((batch,), jnp.int32).at[b_sel].add(
                        valid.astype(jnp.int32))
                    counts = counts.at[s].add(per_img)

            return BatchResult(
                img=jnp.where(valid, b_sel, -1),
                lvl=jnp.where(valid, lvl_sel, -1),
                ys=jnp.where(valid, y_sel, -1),
                xs=jnp.where(valid, x_sel, -1),
                valid=valid, alive_counts=counts, overflow=overflow,
                head_work=head_work)

        def batch_fn(cascade: Cascade, stack: jax.Array,
                     valid_hw: jax.Array) -> BatchResult:
            return tail_fn(cascade, *head_fn(cascade, stack, valid_hw))

        self._batch_heads[plan.key] = head_fn
        self._batch_tails[plan.key] = tail_fn
        return jax.jit(batch_fn)

    def _batch_fn(self, hp: int, wp: int, batch: int):
        plan = self.batch_plan(hp, wp, batch)
        if plan.key not in self._batch_fns:
            self._batch_fns[plan.key] = self._build_batch_fn(plan)
        return self._batch_fns[plan.key]

    def batch_parts(self, hp: int, wp: int, batch: int):
        """The packed batch program's (head_fn, tail_fn) halves, unjitted.

        ``head_fn(cascade, stack, valid_hw)`` runs the per-level dense
        waves and returns the flat pre-compaction state
        ``(alive_flat, inv_flat, ii_flat, counts, head_work)``;
        ``tail_fn(cascade, *that)`` runs the shared compactions + packed
        tail to a :class:`BatchResult`.  Benchmarks jit and time the halves
        directly, so the head/tail split in BENCH_detector is a pair of
        real measurements rather than a subtraction.
        """
        self._batch_fn(hp, wp, batch)    # ensure built (and plan-cached)
        key = self.batch_plan(hp, wp, batch).key
        return self._batch_heads[key], self._batch_tails[key]

    @staticmethod
    def _pack_stack(imgs: list, hp: int, wp: int):
        """Zero-pad a list of images into one (B, hp, wp) stack + their
        true (h, w) shapes — the shared intake of both batch strategies."""
        stack = np.zeros((len(imgs), hp, wp), np.float32)
        valid_hw = np.zeros((len(imgs), 2), np.int32)
        for i, im in enumerate(imgs):
            h, w = im.shape
            stack[i, :h, :w] = im
            valid_hw[i] = (h, w)
        return jnp.asarray(stack), valid_hw

    def detect_batch_raw(self, images) -> list[tuple[LevelResult, float]]:
        """vmap path: per-level batched ``LevelResult``s for a same-bucket
        stack of images (the straightforward `vmap(level_fn)` strategy —
        batched window lists, per-image overflow accounting, shared per-shape
        jit cache with the single-image path)."""
        imgs = [np.asarray(im, np.float32) for im in images]
        hws = {self._bucket_hw(*im.shape) for im in imgs}
        if len(hws) != 1:
            raise ValueError(
                f"detect_batch_raw needs a single shape bucket, got {hws}")
        (hp, wp), = hws
        stack, valid_hw = self._pack_stack(imgs, hp, wp)
        out = []
        for lp in self.batch_plan(hp, wp).levels:
            ys_idx = downscale_indices(hp, lp.height)
            xs_idx = downscale_indices(wp, lp.width)
            img_l = stack[:, ys_idx[:, None], xs_idx[None, :]]
            lims = np.stack(_window_limits(
                valid_hw[:, 0], valid_hw[:, 1], lp.height, lp.width,
                hp, wp), axis=1).astype(np.int32)
            res = self._vmap_level_fn(lp.height, lp.width, len(imgs))(
                self.cascade, img_l, jnp.asarray(lims))
            out.append((res, lp.scale))
        return out

    def detect_batch(self, images, group: bool = True,
                     strategy: str = "packed") -> list[np.ndarray]:
        """Detect faces in many images; returns one (M, 4) rect array per
        image, bit-identical per image to sequential :meth:`detect`.

        Images are grouped into shape buckets (``EngineConfig.pad_multiple``)
        and each bucket runs one program per (bucket shape, sub-batch size).
        ``strategy="packed"`` shares one survivor compaction across the whole
        batch and pyramid (fast tail); ``strategy="vmap"`` runs per-level
        vmapped ``LevelResult``s (per-image overflow attribution).
        """
        imgs = [np.asarray(im, np.float32) for im in images]
        out: list = [None] * len(imgs)
        buckets: dict[tuple[int, int], list[int]] = {}
        for i, im in enumerate(imgs):
            buckets.setdefault(self._bucket_hw(*im.shape), []).append(i)
        for (hp, wp), idxs in buckets.items():
            if strategy == "packed":
                per_img_rects = self._detect_bucket_packed(
                    [imgs[i] for i in idxs], hp, wp)
            elif strategy == "vmap":
                per_img_rects = self._detect_bucket_vmap(
                    [imgs[i] for i in idxs], idxs)
            else:
                raise ValueError(f"unknown batch strategy: {strategy!r}")
            for i, rects in zip(idxs, per_img_rects):
                if group:
                    with obs.span("nms.group", n=len(rects)):
                        rects = nms.group_rectangles(
                            rects, self.config.min_neighbors)
                out[i] = rects
        return out

    def batch_result(self, images) -> BatchResult:
        """Pre-NMS survivors of a same-bucket stack: the :class:`BatchResult`
        of the packed batch program that ``detect_batch`` runs (per-stage
        alive counts, surviving windows, overflow flag).  The span
        ``engine.pack`` covers packing, the upload (``bytes``) and the
        dispatch; the result is not waited for."""
        with obs.span("engine.pack", n=len(images)) as attrs:
            imgs = [np.asarray(im, np.float32) for im in images]
            hws = {self._bucket_hw(*im.shape) for im in imgs}
            if len(hws) != 1:
                raise ValueError(
                    f"batch_result needs a single shape bucket, got {hws}")
            (hp, wp), = hws
            stack, valid_hw = self._pack_stack(imgs, hp, wp)
            valid_hw = jnp.asarray(valid_hw)
            attrs["bytes"] = stack.nbytes + valid_hw.nbytes
            return self._batch_fn(hp, wp, len(imgs))(
                self.cascade, stack, valid_hw)

    def _detect_bucket_packed(self, imgs: list, hp: int, wp: int) -> list:
        n = len(imgs)
        plan = self.batch_plan(hp, wp, n)
        if not plan.levels:  # bucket smaller than the detection window
            return [np.zeros((0, 4), np.int32) for _ in range(n)]
        res = self.batch_result(imgs)
        # wait here, so that the fetch span times the copies alone
        jax.block_until_ready(res)
        if bool(np.asarray(res.overflow)):
            raise CapacityOverflow(
                "batched-engine shared capacity overflow; raise "
                "batch_capacity_fracs / capacity_fracs (see "
                "Detector.calibrated)")
        with obs.span("engine.fetch") as attrs:
            val, b, lvl, ys, xs, work = host = [
                np.asarray(a) for a in (res.valid, res.img, res.lvl,
                                        res.ys, res.xs, res.head_work)]
            attrs["bytes"] = sum(a.nbytes for a in host)
            attrs["head_work"], attrs["head_dense"] = (
                int(v) for v in work.sum(axis=0))
        with obs.span("engine.decode"):
            scales = np.asarray([lp.scale for lp in plan.levels])
            b, lvl, ys, xs = b[val], lvl[val], ys[val], xs[val]
            out = []
            for i in range(n):
                m = b == i
                out.append(self._decode_rects(ys[m], xs[m], scales[lvl[m]]))
        return out

    def _detect_bucket_vmap(self, imgs: list, idxs: list) -> list:
        levels = self.detect_batch_raw(imgs)
        over = np.zeros(len(imgs), bool)
        for res, _ in levels:
            over |= np.asarray(res.overflow)
        if over.any():
            bad = [idxs[i] for i in np.nonzero(over)[0]]
            raise CapacityOverflow(
                f"wave-engine capacity overflow on image(s) {bad}; raise "
                "capacity_fracs (see Detector.calibrated)")
        out = []
        for i in range(len(imgs)):
            rects = []
            for res, scale in levels:
                val = np.asarray(res.valid[i])
                rects.append(self._decode_rects(np.asarray(res.ys[i])[val],
                                                np.asarray(res.xs[i])[val],
                                                scale))
            out.append(np.concatenate(rects, axis=0) if rects
                       else np.zeros((0, 4), np.int32))
        return out

    # ---------------------------------------------------------- calibration
    def calibrated(self, image, safety: float = 2.0,
                   tune_tail: bool = False,
                   tail_sizes: tuple | None = None,
                   tune_head: bool = False) -> "Detector":
        """Profile-guided detector: run once on ``image`` with the current
        (conservative) capacities, measure survivors at each compaction
        boundary, and return a new :class:`Detector` whose
        ``capacity_fracs`` are the worst-level measured fractions with a
        ``safety`` multiplier.  The batched engine's shared capacities
        (``batch_capacity_fracs``) are calibrated from the *summed* survivor
        counts across levels, which is what turns the packed tail into a
        real speedup (see ``benchmarks/bench_serving.py``).

        With ``tune_tail=True`` the packed-tail backends are additionally
        *raced* at capacity-ladder sizes (``packed_tail.measure_rungs``)
        on the profiled image's *real* multi-level packed workload — the
        plan's pyramid levels, each weighted by its measured survivor
        density — and the winners persisted in ``EngineConfig.tail_rungs``,
        so every consumer of the config — batched detection and the
        serving layer — inherits the measured kernel-vs-gather crossover.

        With ``tune_head=True`` the dense *head* is autotuned on the same
        workload (``kernels.autotune``): fused megakernel vs split
        three-dispatch path raced per pyramid level (winners persisted as
        the ``EngineConfig.head_rungs`` ladder + ``head_mode="auto"``),
        head tile shapes raced (winner in ``head_tile``), and packed-tail
        lane-block shapes raced (winner in ``lane_block``).  The plan
        compiler is the single consumer of all of it — re-running
        ``calibrated(tune_tail=True, tune_head=True)`` on hardware is a
        full re-measurement.  The returned detector's ``cal_profile``
        records the per-compaction survivor densities (overall and per
        level), the tuned shapes (``head_tiles`` / ``lane_block`` next to
        ``tail_rungs``), and the timing sweeps for benchmarks."""
        image = np.asarray(image, np.float32)
        h, w = image.shape
        hp, wp = self._bucket_hw(h, w)
        bplan = self.batch_plan(hp, wp)       # per-level window counts
        levels = self.detect_raw(image)
        comp_stages = [seg.s0 for seg in bplan.segments if not seg.dense]
        if not comp_stages:  # dense mode: single final compaction
            comp_stages = [self.n_stages]
        fracs = np.zeros(len(comp_stages))          # worst level, per comp
        surv_tot = np.zeros(len(comp_stages))       # summed over levels
        level_density: list[float] = []             # first compaction, per lv
        win_tot = 0
        for lp, (res, _scale) in zip(bplan.levels, levels):
            nwin = max(lp.n_windows, 1)
            win_tot += nwin
            cnt = np.asarray(res.alive_counts, np.float64)
            for k, s0 in enumerate(comp_stages):
                survivors = cnt[s0 - 1] if s0 > 0 else float(nwin)
                fracs[k] = max(fracs[k], survivors / nwin)
                surv_tot[k] += survivors
                if k == 0:
                    level_density.append(survivors / nwin)
        # same safety shaping as calibrate_capacities, on both schedules
        densities = (surv_tot / max(win_tot, 1)).tolist()
        fracs = calibrate_capacities(fracs, 1, safety)
        batch_fracs = calibrate_capacities(surv_tot, win_tot, safety)
        cfg = self.config._replace(capacity_fracs=fracs,
                                   batch_capacity_fracs=batch_fracs)
        profile: dict = {
            "densities": densities, "n_windows": int(win_tot),
            "level_densities": level_density,
            "levels": [(lp.height, lp.width, lp.n_windows)
                       for lp in bplan.levels],
        }
        if tune_tail or tune_head:
            # real workload: the profiled image at every pyramid level of
            # the plan, each level weighted by its expected packed-window
            # share (density * window count) — closes the synthetic
            # single-level gap for skewed pyramids
            padded = image
            if (hp, wp) != (h, w):
                padded = np.pad(image, ((0, hp - h), (0, wp - w)))
            padded_j = jnp.asarray(padded)
            workload = [
                (np.asarray(downscale_nearest(padded_j, lp.height,
                                              lp.width)),
                 d * lp.n_windows)
                for lp, d in zip(bplan.levels, level_density)]
        if tune_tail:
            kw = {} if tail_sizes is None else {"sizes": tuple(tail_sizes)}
            tail = packed_tail.measure_rungs(self.cascade, workload=workload,
                                             **kw)
            cfg = cfg._replace(tail_backend="auto", tail_rungs=tail["rungs"])
            profile["tail"] = tail
        if tune_head:
            from repro.kernels import autotune as kernels_autotune
            n_dense = bplan.dense_prefix
            if n_dense > 0:
                head = kernels_autotune.measure_head(
                    self.cascade, workload, n_dense=n_dense)
                cfg = cfg._replace(head_mode="auto",
                                   head_rungs=head["rungs"],
                                   head_tile=head["head_tiles"])
                profile["head"] = head
                profile["head_tiles"] = head["head_tiles"]
            if "pallas" in packed_tail.tail_backends():
                lane_size = (profile["tail"]["crossover"]
                             if tune_tail and profile["tail"]["crossover"] > 0
                             else 2048)
                lane = kernels_autotune.measure_lane_block(
                    self.cascade, workload, size=lane_size)
                cfg = cfg._replace(lane_block=lane["lane_block"])
                profile["lane"] = lane
                profile["lane_block"] = lane["lane_block"]
        det = Detector(self.cascade, cfg)
        det.cal_profile = profile
        return det

    # ------------------------------------------------------------- analysis
    def work_profile(self, image) -> dict:
        """Windows / weak-eval accounting per level — the cost model input
        for the scheduling layer (tasks = pyramid levels / tiles) and the
        reproduction of the paper's profile breakdown (Fig. 13)."""
        levels = self.detect_raw(image)
        sizes = self.cascade.stage_sizes().astype(np.int64)
        img = np.asarray(image)
        hp, wp = self._bucket_hw(img.shape[0], img.shape[1])
        bplan = self.batch_plan(hp, wp)   # per-level window counts
        total_windows = 0
        weak_early = 0   # ideal per-stage early exit (sequential semantics)
        weak_dense = 0   # delayed rejection
        per_level = []
        for lp, (res, scale) in zip(bplan.levels, levels):
            nwin = lp.n_windows
            counts = np.asarray(res.alive_counts, np.int64)
            alive_before = np.concatenate([[nwin], counts[:-1]])
            we = int((alive_before * sizes).sum())
            wd = int(nwin * sizes.sum())
            weak_early += we
            weak_dense += wd
            total_windows += nwin
            per_level.append({
                "scale": scale, "windows": nwin,
                "alive_counts": counts, "weak_evals_early": we,
                "weak_evals_dense": wd,
            })
        return {
            "total_windows": total_windows,
            "weak_evals_early_exit": weak_early,
            "weak_evals_dense": weak_dense,
            "per_level": per_level,
        }
