"""Streaming video detection with temporal tile-reuse.

:class:`VideoDetector` wraps a calibrated :class:`repro.core.Detector` for
one video stream.  Per frame it:

1. scores each tile of the frame against the stream's *reference frame*
   (the pixels the cached decisions were computed on — not simply the
   previous frame, so sub-threshold drift never compounds silently);
2. maps changed tiles (plus a dilated halo) to the exact set of detection
   windows whose receptive field they overlap, per pyramid level; the
   levels with any changed window form the frame's *active level subset*
   (``FramePlan.active_levels``);
3. re-evaluates only those windows through the packed incremental engine
   (:class:`repro.stream.StreamEngine`), which compiles a level-subset
   program: fully-cached levels build no SAT at all.  Survivors merge
   into the cached per-level bitmaps; everything else is reused.

Exactness: with ``threshold <= 0`` a tile is "changed" iff any pixel
differs, so the cache always reflects the current frame's pixels exactly
and the output is **bit-identical** to running ``Detector.detect`` on
every frame (same windows, same order, same grouping).  With a positive
threshold, cached decisions may lag the true frame by at most the
per-tile score threshold; a periodic keyframe (``keyframe_interval``)
re-detects the whole frame and bounds the staleness window.

Fallbacks keep the fast path honest: if the changed-window fraction
exceeds ``full_refresh_frac``, or the packed list overflows its static
capacity, the frame is re-detected in full (same result, no drift).

The plan/commit split (``plan_frame`` / ``commit_*``) exists so the
serving layer can batch work *across* streams: many sessions' changed
windows share one packed compaction, and many sessions' keyframes share
one ``detect_batch`` flush.  ``process`` composes the two for the
single-stream case.
"""

from __future__ import annotations

import itertools
from collections import deque
from typing import NamedTuple

import numpy as np
import jax

from repro import obs
from repro.core.engine import Detector
from repro.core import nms
from .engine import StreamEngine, StreamGeometry
from .tiles import (tile_grid_shape, tile_change_scores, dilate_tiles,
                    changed_window_mask)

__all__ = ["StreamConfig", "FrameStats", "FramePlan", "VideoDetector",
           "level_windows_from_raw"]

_MODES = ("cached", "incremental", "full")
_SIDS = itertools.count()


def level_windows_from_raw(levels, index: int | None = None
                           ) -> list[tuple[np.ndarray, np.ndarray]]:
    """Surviving (ys, xs) per pyramid level from a raw detector pass.

    ``levels`` is ``Detector.detect_raw`` output (``index=None``) or the
    batched ``detect_batch_raw`` output (``index`` = image position); the
    single decode/overflow policy for every keyframe path, single-stream
    and service-batched alike."""
    wins = []
    for res, _scale in levels:
        # repro: ignore[HOST_SYNC] keyframe decode: raw survivor arrays are this path's output
        over = np.asarray(res.overflow)
        if bool(over if index is None else over[index]):
            raise RuntimeError(
                "wave-engine capacity overflow on stream keyframe; raise "
                "capacity_fracs (see Detector.calibrated)")
        # repro: ignore[HOST_SYNC] keyframe decode: raw survivor arrays are this path's output
        ys = np.asarray(res.ys if index is None else res.ys[index])
        # repro: ignore[HOST_SYNC] keyframe decode: raw survivor arrays are this path's output
        xs = np.asarray(res.xs if index is None else res.xs[index])
        # repro: ignore[HOST_SYNC] keyframe decode: raw survivor arrays are this path's output
        val = np.asarray(res.valid if index is None else res.valid[index])
        wins.append((ys[val], xs[val]))
    return wins


class StreamConfig(NamedTuple):
    tile: int = 32                 # tile edge, image coords
    threshold: float = 0.0         # mean-sq change per pixel; <=0 = exact
    halo: int = 1                  # dilation rings around changed tiles
    keyframe_interval: int = 64    # full re-detect cadence; 0 = never
    max_changed_frac: float = 0.5  # incremental budget as a window fraction
    full_refresh_frac: float = 0.5  # changed-window frac forcing full detect
    # ---- graceful-degradation knobs (fleet serving under overload).
    # degraded(level) stretches the keyframe cadence and raises the change
    # threshold; it never touches tile/halo, so the conservative
    # changed-tile -> window mapping (every window whose receptive field
    # overlaps a changed tile is recomputed) is preserved at every level.
    degrade_keyframe_mult: float = 2.0   # keyframe_interval x this / level
    degrade_threshold_add: float = 0.0   # change-score added per level (0 =
    #                                      keyframe stretch only, keeps
    #                                      threshold-0 streams bit-exact)
    max_degrade_level: int = 3
    # ---- device-resident state.  True moves the reference frame, survivor
    # bitmap and frame counters onto the device as a donated pytree: per
    # frame, change scoring, window mapping, the cached/incremental/full
    # decision and the evaluation of every window to recompute — changed
    # windows, or the whole frame on a keyframe or full refresh — run in
    # one jitted step, one program per stream shape and exactness.  The
    # host uploads the new frame and fetches a handful of scalars plus the
    # survivor slot list.  Frames go through submit/retire (process
    # composes them); at threshold<=0 the output stays bit-identical to
    # the host-planned path and to per-frame Detector.detect.
    # max_changed_frac does not apply here: the step has no capacity.
    device_state: bool = False

    def degraded(self, level: int) -> "StreamConfig":
        """The stretched config at degradation ``level`` (0 = this config).

        Level is clamped to ``max_degrade_level``.  Each level multiplies
        the keyframe interval by ``degrade_keyframe_mult`` (0 = never stays
        never) and adds ``degrade_threshold_add`` to the change threshold;
        with the default additive step of 0, a threshold-0 (exact) stream
        stays bit-identical to per-frame detection at every level — only
        its full-refresh cadence stretches."""
        level = max(0, min(int(level), self.max_degrade_level))
        if level == 0:
            return self
        kf = self.keyframe_interval
        if kf > 0:
            kf = max(int(round(kf * self.degrade_keyframe_mult ** level)), kf)
        thr = self.threshold + self.degrade_threshold_add * level
        return self._replace(keyframe_interval=kf, threshold=thr)


class FrameStats(NamedTuple):
    frame_idx: int
    mode: str                      # 'full' | 'incremental' | 'cached'
    tiles_total: int
    tiles_changed: int             # after halo dilation
    windows_total: int             # live (limit-valid) windows, all levels
    windows_recomputed: int
    levels_total: int = 0          # pyramid levels in the bucket's plan
    levels_active: int = 0         # levels whose SAT/head ran this frame

    @property
    def tile_skip_frac(self) -> float:
        return 1.0 - self.tiles_changed / max(self.tiles_total, 1)

    @property
    def window_skip_frac(self) -> float:
        return 1.0 - self.windows_recomputed / max(self.windows_total, 1)

    @property
    def level_skip_frac(self) -> float:
        """Fraction of pyramid levels whose dense-wave/SAT head was skipped
        (fully cached) this frame."""
        return 1.0 - self.levels_active / max(self.levels_total, 1)


class FramePlan(NamedTuple):
    mode: str                      # 'full' | 'incremental' | 'cached'
    masks: list | None             # per-level flat recompute masks
    changed_tiles: np.ndarray | None   # dilated tile mask
    tiles_changed: int
    windows_to_recompute: int
    active_levels: tuple[int, ...] | None = None   # levels with changed
    #                                windows ('incremental' plans only; the
    #                                incremental engine builds SATs for
    #                                exactly this subset)


class _DevToken:
    """One in-flight frame of a device-resident stream.

    Created (and its step dispatched) by :meth:`VideoDetector.submit`,
    resolved by ``poll`` and finished by ``commit_token`` (``retire``
    composes them).  ``out`` holds the step's device arrays while the
    frame is in flight — fetching them is the only host sync of a frame.
    """
    __slots__ = ("frame", "out", "flags")

    def __init__(self, frame: np.ndarray):
        self.frame = frame          # (h, w) f32 host pixels (for fallbacks)
        self.out = None             # StreamStepOut device arrays
        self.flags = None           # fetched scalar tuple, set by poll


class VideoDetector:
    """One stream's temporal state over a shared :class:`Detector`."""

    def __init__(self, detector: Detector, config: StreamConfig = StreamConfig(),
                 engine: StreamEngine | None = None, *,
                 decode_cap: int | None = None):
        self.detector = detector
        self.config = config
        self.engine = engine or StreamEngine(detector,
                                             config.max_changed_frac)
        self._shape: tuple[int, int] | None = None
        self._geo: StreamGeometry | None = None
        self._limits: list[tuple[int, int]] = []
        self._n_live = 0
        self._tile_grid: tuple[int, int] = (0, 0)
        self._tiles_total = 0
        self._scales: np.ndarray | None = None
        self._ref: np.ndarray | None = None         # reference pixels
        self._bitmap: np.ndarray | None = None      # flat survivor cache
        self._rects: np.ndarray | None = None       # cached grouped output
        self._frame_idx = 0
        self._last_full = -1
        # ---- device-resident state (config.device_state)
        self._decode_cap = decode_cap     # override for the slot-list size
        self._splan = None                # StreamStatePlan, built at open
        self._dev_state = None            # donated StreamState pytree
        self._pending: deque[_DevToken] = deque()   # in-flight frames, FIFO
        self._last_mode = "full"          # last committed frame's mode
        self.xfer_bytes = 0               # host<->device traffic accounting
        self.slot_builds = 0              # steps that rebuilt the slot list
        self.sid = next(_SIDS)            # this stream's id in its spans

    # ------------------------------------------------------------ plumbing
    @property
    def frame_idx(self) -> int:
        return self._frame_idx

    @property
    def bucket_hw(self) -> tuple[int, int] | None:
        return None if self._geo is None else (self._geo.hp, self._geo.wp)

    def _init_stream(self, frame: np.ndarray) -> None:
        h, w = frame.shape
        self._shape = (h, w)
        hp, wp = self.detector._bucket_hw(h, w)
        self._geo = self.engine.geometry(hp, wp)
        self._limits = self._geo.limits(h, w)
        self._n_live = 0
        for (ny, nx), (y_lim, x_lim) in zip(self._geo.level_windows,
                                            self._limits):
            n_y = min(int(y_lim) // self._geo.step + 1, ny) if y_lim >= 0 else 0
            n_x = min(int(x_lim) // self._geo.step + 1, nx) if x_lim >= 0 else 0
            self._n_live += n_y * n_x
        # per-frame constants, computed once at open (not per _finish call)
        ty, tx = tile_grid_shape(h, w, self.config.tile)
        self._tile_grid = (ty, tx)
        self._tiles_total = ty * tx
        # repro: ignore[HOST_SYNC] host constant from plan metadata, no device round-trip
        self._scales = np.asarray([lv.scale for lv in self._geo.plan]) \
            if self._geo.plan else np.zeros(0)
        if self.config.device_state and self._geo.n_slots > 0:
            self._splan = self.engine.stream_plan(
                hp, wp, h, w, self.config.tile, self.config.halo,
                decode_cap=self._decode_cap)

    def _check_frame(self, frame) -> np.ndarray:
        # repro: ignore[HOST_SYNC] frame intake: callers hand in host pixels
        frame = np.asarray(frame, np.float32)
        if frame.ndim != 2:
            raise ValueError(f"expected grayscale (H, W) frame, got "
                             f"shape {frame.shape}")
        if self._shape is None:
            self._init_stream(frame)
        elif frame.shape != self._shape:
            raise ValueError(f"stream frame shape changed: {self._shape} -> "
                             f"{frame.shape}; open a new stream instead")
        return frame

    # ------------------------------------------------------------ planning
    def plan_frame(self, frame) -> tuple[np.ndarray, FramePlan]:
        """Decide how to process ``frame``; returns (frame_f32, plan)."""
        frame = self._check_frame(frame)
        cfg = self.config
        geo = self._geo
        if self._splan is not None:
            raise RuntimeError(
                "device-resident stream: planning happens on device — use "
                "submit/poll/commit_token (or process) instead of "
                "plan_frame")
        if self._ref is None:
            return frame, FramePlan("full", None, None, 0, 0)
        if geo.n_slots == 0:       # frame smaller than the detection window
            return frame, FramePlan("cached", None, None, 0, 0)
        due = (cfg.keyframe_interval > 0 and
               self._frame_idx - self._last_full >= cfg.keyframe_interval)
        if due:
            return frame, FramePlan("full", None, None, 0, 0)
        exact = cfg.threshold <= 0
        scores, changed_any = tile_change_scores(self._ref, frame, cfg.tile,
                                                 exact=exact)
        changed = changed_any if exact else (scores > cfg.threshold)
        changed = dilate_tiles(changed, cfg.halo)
        n_changed = int(changed.sum())
        if n_changed == 0:
            return frame, FramePlan("cached", None, changed, 0, 0)
        # tile fraction under-estimates the window fraction (receptive
        # fields cover multiple tiles), so this is a safe early exit that
        # skips per-level mask building when a refresh is certain anyway
        if n_changed > cfg.full_refresh_frac * changed.size:
            return frame, FramePlan("full", None, changed, n_changed, 0)
        masks = [changed_window_mask(changed, cfg.tile, geo.hp, geo.wp,
                                     lv, geo.step, y_lim, x_lim)
                 for lv, (y_lim, x_lim) in zip(geo.plan, self._limits)]
        n_rec = int(sum(int(m.sum()) for m in masks))
        if n_rec > cfg.full_refresh_frac * max(self._n_live, 1):
            return frame, FramePlan("full", None, changed, n_changed, n_rec)
        active = tuple(li for li, m in enumerate(masks) if m.any())
        return frame, FramePlan("incremental", masks, changed,
                                n_changed, n_rec, active)

    # ------------------------------------------------------------- commits
    def _decode_slots(self, idxs: np.ndarray) -> np.ndarray:
        """Grouped rects from a list of surviving flat slot indices.

        The single decode path for host bitmaps and device slot lists; the
        returned array is marked read-only so cached frames can hand the
        same object back without a per-frame copy."""
        geo = self._geo
        with obs.span("stream.decode"):
            if len(idxs) == 0:
                rects = np.zeros((0, 4), np.int32)
            else:
                rects = Detector._decode_rects(
                    geo.y_of_slot[idxs], geo.x_of_slot[idxs],
                    self._scales[geo.lvl_of_slot[idxs]])
            with obs.span("nms.group", n=len(rects)):
                rects = nms.group_rectangles(
                    rects, self.detector.config.min_neighbors)
        rects.setflags(write=False)
        return rects

    def _decode(self) -> np.ndarray:
        return self._decode_slots(np.nonzero(self._bitmap)[0])

    def _finish(self, frame: np.ndarray, mode: str, tiles_changed: int,
                recomputed: int, levels_active: int
                ) -> tuple[np.ndarray, FrameStats]:
        self._rects = self._decode() if mode != "cached" else self._rects
        stats = FrameStats(self._frame_idx, mode, self._tiles_total,
                           tiles_changed, self._n_live, recomputed,
                           len(self._geo.plan), levels_active)
        self._frame_idx += 1
        self._last_mode = mode
        # read-only (see _decode_slots): cached frames return the same
        # array, copy-free — callers must not mutate it
        return self._rects, stats

    def _slots_of(self, level_windows: list[tuple[np.ndarray, np.ndarray]]
                  ) -> np.ndarray:
        """Flat survivor slots of a raw detector pass's (ys, xs) per
        level, ascending."""
        geo = self._geo
        parts = []
        for li, (ys, xs) in enumerate(level_windows):
            if len(ys) == 0:
                continue
            ny, nx = geo.level_windows[li]
            # keyframe decode: the raw survivor coords are this path's input
            ys = np.asarray(ys)  # repro: ignore[HOST_SYNC] keyframe decode input
            xs = np.asarray(xs)  # repro: ignore[HOST_SYNC] keyframe decode input
            parts.append(geo.slot_offsets[li] + (ys // geo.step) * nx
                         + xs // geo.step)
        return (np.sort(np.concatenate(parts)) if parts
                else np.zeros(0, np.int64))

    def commit_full(self, frame: np.ndarray,
                    level_windows: list[tuple[np.ndarray, np.ndarray]] | None
                    = None) -> tuple[np.ndarray, FrameStats]:
        """Full re-detect of a host-planned stream: refresh every cached
        decision from ``frame``.

        ``level_windows`` (surviving (ys, xs) per pyramid level, as produced
        by the detector's raw paths) lets the serving layer batch many
        streams' keyframes through ``detect_batch_raw`` and feed each
        session its slice; when omitted the detector runs directly.
        """
        geo = self._geo
        if level_windows is None:
            level_windows = level_windows_from_raw(
                self.detector.detect_raw(frame))
        # full-detect traffic: frame up, surviving window coords back down
        self.xfer_bytes += frame.nbytes + sum(
            ys.nbytes + xs.nbytes for ys, xs in level_windows)
        bitmap = np.zeros(geo.n_slots, bool)
        bitmap[self._slots_of(level_windows)] = True
        self._bitmap = bitmap
        self._ref = frame.copy()
        self._last_full = self._frame_idx
        return self._finish(frame, "full", self._tiles_total, self._n_live,
                            len(geo.plan))

    def commit_incremental(self, frame: np.ndarray, plan: FramePlan,
                           survivors_flat: np.ndarray
                           ) -> tuple[np.ndarray, FrameStats]:
        """Merge recomputed survivors into the cache; update the reference
        pixels under every recomputed tile."""
        mask_flat = np.concatenate(plan.masks)
        self._bitmap = (self._bitmap & ~mask_flat) | survivors_flat
        h, w = self._shape
        tile = self.config.tile
        pix = np.repeat(np.repeat(plan.changed_tiles, tile, axis=0),
                        tile, axis=1)[:h, :w]
        self._ref = np.where(pix, frame, self._ref)
        return self._finish(frame, "incremental", plan.tiles_changed,
                            plan.windows_to_recompute,
                            len(plan.active_levels or ()))

    def commit_cached(self, frame: np.ndarray,
                      plan: FramePlan) -> tuple[np.ndarray, FrameStats]:
        return self._finish(frame, "cached", plan.tiles_changed, 0, 0)

    # ------------------------------------------- device-resident fast path
    def submit(self, frame) -> _DevToken:
        """Queue ``frame`` on the device-resident stream, dispatch its
        plan-and-eval step and return its token.  jax dispatch is async
        and every step commits (the donated state chains from one frame's
        step to the next), so frame N+1's step runs while the host decodes
        frame N.  Tokens must be retired in submit order."""
        if not self.config.device_state:
            raise RuntimeError(
                "submit/retire need StreamConfig.device_state=True; use "
                "process/plan_frame on host-planned streams")
        frame = self._check_frame(frame)
        tok = _DevToken(frame)
        if self._splan is not None:
            if self._dev_state is None:   # opening frame, or after reset
                self._dev_state = self.engine.init_state(
                    self._splan, self._frame_idx + len(self._pending))
            self._dispatch_token(tok)
        self._pending.append(tok)
        return tok

    def _dispatch_token(self, tok: _DevToken) -> None:
        """Run the device step for ``tok``'s frame, donating the state."""
        cfg = self.config
        splan = self._splan
        with obs.span("stream.step", sess=self.sid,
                      frame=self._frame_idx + len(self._pending)):
            padded = np.zeros((splan.hp, splan.wp), np.float32)
            padded[:splan.h, :splan.w] = tok.frame
            fn = self.engine.stream_step(splan, cfg.threshold <= 0,
                                         cfg.full_refresh_frac)
            self._dev_state, tok.out = fn(
                self.detector.cascade, self._dev_state, padded,
                np.float32(cfg.threshold), np.int32(cfg.keyframe_interval))
        self.xfer_bytes += padded.nbytes

    def poll(self, tok: _DevToken) -> str:
        """Resolve ``tok``'s frame mode — ``'cached'``, ``'incremental'``
        or ``'full'`` (a keyframe or full refresh, committed on the device
        like the others) — and finish it with :meth:`commit_token`.
        Waits for the device step, then copies its verdict scalars and
        head-work counters.  ``tok.flags`` holds (mode, tiles changed,
        windows recomputed, levels active, survivors, slot list rebuilt)."""
        if not self._pending or tok is not self._pending[0]:
            raise RuntimeError("device tokens must be polled/retired in "
                               "submit order")
        if self._splan is None:
            # frame smaller than the detection window: nothing to run
            tok.flags = (2 if self._rects is None else 0,) + (0,) * 5
            return _MODES[tok.flags[0]]
        out = tok.out
        jax.block_until_ready(out.n_surv)
        with obs.span("stream.poll") as attrs:
            # repro: ignore[HOST_SYNC] contract sync: the step's scalar verdict is what poll exists to fetch
            flags = jax.device_get((out.mode, out.tiles_changed, out.n_rec,
                                    out.levels_active, out.n_surv,
                                    out.slots_built, out.head_tiles,
                                    out.dense_tiles))
            tok.flags = tuple(int(v) for v in flags[:6])
            attrs.update(zip(("mode", "n_tiles", "n_rec", "levels_active"),
                             tok.flags[:4]))
            attrs["slots_built"] = tok.flags[5]
            attrs.update(self._head_counts(flags[6], int(flags[7])))
        self.slot_builds += tok.flags[5]
        self.xfer_bytes += (7 + self.detector.n_stages) * 4
        return _MODES[tok.flags[0]]

    def _head_counts(self, head_tiles, dense_tiles: int) -> dict:
        """The head's work of one step in the units of the photo path's
        ``engine.fetch`` counters: weak-classifier evaluations of head
        tiles (``head_work``) and those of a head without exit on the
        levels the step evaluated (``head_dense``); ``head_windows`` per
        stage counts the windows of the tiles that ran it."""
        n_weak = self.engine.n_weak
        area = int(np.prod(self.engine.head_tile))
        # repro: ignore[HOST_SYNC] counters already fetched with the verdict
        head_tiles = np.asarray(head_tiles, np.int64)
        return {"head_work": int((head_tiles * n_weak).sum()),
                "head_dense": int(dense_tiles * n_weak.sum()),
                "head_windows": tuple(int(t) * area for t in head_tiles)}

    def commit_token(self, tok: _DevToken) -> tuple[np.ndarray, FrameStats]:
        """Finish a polled token: fetch the decoded survivor slots (frames
        the step evaluated), group rects, and mirror the host path's
        stats and engine counters."""
        mode = _MODES[tok.flags[0]]
        n_tiles, n_rec, lvls, n_surv = tok.flags[1:5]
        self._pending.popleft()
        n_levels = len(self._geo.plan)
        if mode == "full":
            n_tiles, n_rec, lvls = self._tiles_total, self._n_live, n_levels
        if self._splan is None:
            if self._rects is None:
                self._rects = self._decode_slots(np.zeros(0, np.int64))
        elif n_surv > self._splan.decode_cap:
            # survivor count overflows the static slot list (decode only —
            # the committed device bitmap is fine): the frame's rects come
            # from a host detect, identical at threshold 0, and it counts
            # as a full frame
            slots = self._slots_of(level_windows_from_raw(
                self.detector.detect_raw(tok.frame)))
            self.xfer_bytes += tok.frame.nbytes
            self._rects = self._decode_slots(slots)
            mode = "full"
            n_tiles, n_rec, lvls = self._tiles_total, self._n_live, n_levels
        elif mode != "cached":
            if mode == "incremental":
                self.engine.dispatches += 1
                self.engine.sat_level_builds += lvls
                self.engine.sat_level_total += n_levels
            with obs.span("stream.fetch") as attrs:
                # repro: ignore[HOST_SYNC] contract sync: decoded survivor slots are the frame's output
                slots = np.asarray(jax.device_get(tok.out.slots))[:n_surv]
                attrs["bytes"] = self._splan.decode_cap * 4
            self.xfer_bytes += self._splan.decode_cap * 4
            self._rects = self._decode_slots(slots)
        stats = FrameStats(self._frame_idx, mode, self._tiles_total,
                           n_tiles, self._n_live, n_rec, n_levels, lvls)
        self._frame_idx += 1
        self._last_mode = mode
        return self._rects, stats

    def retire(self, tok: _DevToken) -> tuple[np.ndarray, FrameStats]:
        """Block on ``tok`` and finish its frame."""
        self.poll(tok)
        return self.commit_token(tok)

    def reconfigure(self, config: StreamConfig) -> None:
        """Swap the stream's threshold/keyframe policy mid-stream without
        dropping temporal state — the serving layer's degradation path
        (``config.degraded(level)``).  ``tile`` and ``halo`` must not
        change: the cached bitmaps stay valid under any threshold/cadence,
        but the change-detection granularity is part of the stream's
        conservative-mapping contract and is fixed at open time."""
        if (config.tile, config.halo) != (self.config.tile, self.config.halo):
            raise ValueError(
                f"tile/halo are fixed per stream: "
                f"{(self.config.tile, self.config.halo)} -> "
                f"{(config.tile, config.halo)}; open a new stream instead")
        if config.device_state != self.config.device_state:
            raise ValueError(
                "device_state is fixed per stream (the temporal state "
                "lives on one side); open a new stream instead")
        self.config = config

    # -------------------------------------------------------------- public
    def process(self, frame) -> tuple[np.ndarray, FrameStats]:
        """Detect faces in the next frame of this stream.

        Returns ``(rects, stats)`` with rects exactly as
        ``Detector.detect`` would format them (the array is read-only and
        shared across cached frames — copy before mutating).
        """
        if self.config.device_state:
            return self.retire(self.submit(frame))
        frame, plan = self.plan_frame(frame)
        return self.commit_planned(frame, plan)

    def commit_planned(self, frame: np.ndarray, plan: FramePlan
                       ) -> tuple[np.ndarray, FrameStats]:
        """Execute a host-planned frame: the commit half of ``process``
        (benchmarks time the plan/commit phases through this split)."""
        if plan.mode == "cached":
            return self.commit_cached(frame, plan)
        if plan.mode == "full":
            return self.commit_full(frame)
        geo = self._geo
        bitmaps, _rec, overflow = self.engine.incremental(
            [frame], [plan.masks], geo.hp, geo.wp,
            active=plan.active_levels)
        # frame stack up; recompute masks up, survivor bitmap back down
        self.xfer_bytes += geo.hp * geo.wp * 4 + 2 * geo.n_slots
        if overflow:   # too many changed windows for the packed capacity
            return self.commit_full(frame)
        return self.commit_incremental(frame, plan, bitmaps[0])

    def reset(self) -> None:
        """Drop all temporal state (next frame is a keyframe)."""
        self._ref = None
        self._bitmap = None
        self._rects = None
        self._last_full = -1
        self._dev_state = None
        self._pending.clear()
        self._last_mode = "full"
