"""Streaming video detection with temporal tile-reuse.

:class:`VideoDetector` wraps a calibrated :class:`repro.core.Detector` for
one video stream whose temporal state lives on the device.  Per frame, one
jitted step (:meth:`repro.stream.StreamEngine.stream_step`):

1. scores each tile of the frame against the stream's *reference frame*
   (the pixels the cached decisions were computed on — not simply the
   previous frame, so sub-threshold drift never compounds silently);
2. maps changed tiles (plus a dilated halo) to the exact set of detection
   windows whose receptive field they overlap, per pyramid level;
3. decides cached / incremental / full and evaluates every window to
   recompute — the changed windows, or every live window on a keyframe or
   a full refresh — merging the survivors into the cached bitmap.

The host uploads the frame, fetches the step's scalar verdict and, on
frames that evaluated windows, the decoded survivor slots, then groups
rects.  ``submit`` dispatches a frame's step and ``retire`` finishes it
(``poll`` + ``commit_token``); ``process`` composes them.  jax dispatch is
async and the donated state chains from step to step, so a caller may
submit frame N+1 before retiring frame N.

Exactness: with ``threshold <= 0`` a tile is "changed" iff any pixel
differs, so the cache always reflects the current frame's pixels exactly
and the output is **bit-identical** to running ``Detector.detect`` on
every frame (same windows, same order, same grouping).  With a positive
threshold, cached decisions may lag the true frame by at most the
per-tile score threshold; a periodic keyframe (``keyframe_interval``)
re-detects the whole frame and bounds the staleness window.  A frame
whose changed-window fraction exceeds ``full_refresh_frac`` is re-detected
in full in the same step.
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

__all__ = ["StreamConfig", "FrameStats", "VideoDetector",
           "level_windows_from_raw"]

_MODES = ("cached", "incremental", "full")
_SIDS = itertools.count()


def level_windows_from_raw(levels) -> list[tuple[np.ndarray, np.ndarray]]:
    """Surviving (ys, xs) per pyramid level from ``Detector.detect_raw``
    output: the host detect a frame falls back to when its survivors
    overflow the step's slot list."""
    wins = []
    for res, _scale in levels:
        # repro: ignore[HOST_SYNC] fallback decode: raw survivor arrays are this path's output
        if bool(np.asarray(res.overflow)):
            raise RuntimeError(
                "wave-engine capacity overflow on a stream frame's host "
                "detect; raise capacity_fracs (see Detector.calibrated)")
        # repro: ignore[HOST_SYNC] fallback decode: raw survivor arrays are this path's output
        val = np.asarray(res.valid)
        # repro: ignore[HOST_SYNC] fallback decode: raw survivor arrays are this path's output
        wins.append((np.asarray(res.ys)[val], np.asarray(res.xs)[val]))
    return wins


class StreamConfig(NamedTuple):
    tile: int = 32                 # tile edge, image coords
    threshold: float = 0.0         # mean-sq change per pixel; <=0 = exact
    halo: int = 1                  # dilation rings around changed tiles
    keyframe_interval: int = 64    # full re-detect cadence; 0 = never
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
    # streams are always device-resident; the field remains only because
    # the benchmark's drivers pass device_state=True, and False is refused
    device_state: bool = True

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


def _check_device_state(config: StreamConfig) -> None:
    if not config.device_state:
        raise ValueError("StreamConfig.device_state=False is refused: "
                         "streams are always device-resident")


class FrameStats(NamedTuple):
    frame_idx: int
    mode: str                      # 'full' | 'incremental' | 'cached'
    tiles_total: int
    tiles_changed: int             # after halo dilation
    windows_total: int             # live (limit-valid) windows, all levels
    windows_recomputed: int
    levels_total: int = 0          # pyramid levels in the bucket's plan
    levels_active: int = 0         # levels whose head ran this frame

    @property
    def tile_skip_frac(self) -> float:
        return 1.0 - self.tiles_changed / max(self.tiles_total, 1)

    @property
    def window_skip_frac(self) -> float:
        return 1.0 - self.windows_recomputed / max(self.windows_total, 1)

    @property
    def level_skip_frac(self) -> float:
        """Fraction of pyramid levels whose head was skipped (fully
        cached) this frame."""
        return 1.0 - self.levels_active / max(self.levels_total, 1)


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
    """One stream's device-resident temporal state over a shared
    :class:`Detector`."""

    def __init__(self, detector: Detector, config: StreamConfig = StreamConfig(),
                 engine: StreamEngine | None = None, *,
                 decode_cap: int | None = None):
        _check_device_state(config)
        self.detector = detector
        self.config = config
        self.engine = engine or StreamEngine(detector)
        self._shape: tuple[int, int] | None = None
        self._geo: StreamGeometry | None = None
        self._n_live = 0
        self._tiles_total = 0
        self._scales: np.ndarray | None = None
        self._rects: np.ndarray | None = None       # cached grouped output
        self._frame_idx = 0
        self._decode_cap = decode_cap     # override for the slot-list size
        self._splan = None                # StreamStatePlan, built at open
        self._dev_state = None            # donated StreamState pytree
        self._pending: deque[_DevToken] = deque()   # in-flight frames, FIFO
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
        tile = self.config.tile
        self._shape = (h, w)
        hp, wp = self.detector._bucket_hw(h, w)
        self._geo = self.engine.geometry(hp, wp)
        # repro: ignore[HOST_SYNC] host constant from plan metadata, no device round-trip
        self._scales = np.asarray([lv.scale for lv in self._geo.plan]) \
            if self._geo.plan else np.zeros(0)
        if self._geo.n_slots > 0:
            self._splan = self.engine.stream_plan(
                hp, wp, h, w, tile, self.config.halo,
                decode_cap=self._decode_cap)
            self._n_live = self._splan.n_live
            self._tiles_total = self._splan.ty * self._splan.tx
        else:                      # frame smaller than the detection window
            self._tiles_total = -(-h // tile) * -(-w // tile)

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

    def _decode_slots(self, idxs: np.ndarray) -> np.ndarray:
        """Grouped rects from a list of surviving flat slot indices; the
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
            # fallback decode: the raw survivor coords are this path's input
            ys = np.asarray(ys)  # repro: ignore[HOST_SYNC] fallback decode input
            xs = np.asarray(xs)  # repro: ignore[HOST_SYNC] fallback decode input
            parts.append(geo.slot_offsets[li] + (ys // geo.step) * nx
                         + xs // geo.step)
        return (np.sort(np.concatenate(parts)) if parts
                else np.zeros(0, np.int64))

    # ------------------------------------------------------- frame lifecycle
    def submit(self, frame) -> _DevToken:
        """Queue ``frame`` on the device-resident stream, dispatch its
        plan-and-eval step and return its token.  jax dispatch is async
        and every step commits (the donated state chains from one frame's
        step to the next), so frame N+1's step runs while the host decodes
        frame N.  Tokens must be retired in submit order."""
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
        the step evaluated), group rects, and report the frame's stats."""
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
            with obs.span("stream.fetch") as attrs:
                # repro: ignore[HOST_SYNC] contract sync: decoded survivor slots are the frame's output
                slots = np.asarray(jax.device_get(tok.out.slots))[:n_surv]
                attrs["bytes"] = self._splan.decode_cap * 4
            self.xfer_bytes += self._splan.decode_cap * 4
            self._rects = self._decode_slots(slots)
        stats = FrameStats(self._frame_idx, mode, self._tiles_total,
                           n_tiles, self._n_live, n_rec, n_levels, lvls)
        self._frame_idx += 1
        # read-only (see _decode_slots): cached frames return the same
        # array, copy-free — callers must not mutate it
        return self._rects, stats

    def retire(self, tok: _DevToken) -> tuple[np.ndarray, FrameStats]:
        """Block on ``tok`` and finish its frame."""
        self.poll(tok)
        return self.commit_token(tok)

    def reconfigure(self, config: StreamConfig) -> None:
        """Swap the stream's threshold/keyframe policy mid-stream without
        dropping temporal state — the serving layer's degradation path
        (``config.degraded(level)``).  ``tile`` and ``halo`` must not
        change: the cached bitmap stays valid under any threshold/cadence,
        but the change-detection granularity is part of the stream's
        conservative-mapping contract and is fixed at open time."""
        if (config.tile, config.halo) != (self.config.tile, self.config.halo):
            raise ValueError(
                f"tile/halo are fixed per stream: "
                f"{(self.config.tile, self.config.halo)} -> "
                f"{(config.tile, config.halo)}; open a new stream instead")
        _check_device_state(config)
        self.config = config

    # -------------------------------------------------------------- public
    def process(self, frame) -> tuple[np.ndarray, FrameStats]:
        """Detect faces in the next frame of this stream.

        Returns ``(rects, stats)`` with rects exactly as
        ``Detector.detect`` would format them (the array is read-only and
        shared across cached frames — copy before mutating).
        """
        return self.retire(self.submit(frame))

    def reset(self) -> None:
        """Drop all temporal state (next frame is a keyframe)."""
        self._rects = None
        self._dev_state = None
        self._pending.clear()
