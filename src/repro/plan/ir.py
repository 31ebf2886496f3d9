"""Typed cascade-plan IR: the *model* of one cascade workload.

The paper's scheduling story rests on a model of the cascade's work that
is computed once and consumed by executors (CATS-style cost models lifted
out of the worker loop).  These types are that model for our engines:

- :class:`LevelPlan` — one pyramid level's static geometry (shape, window
  grid, flat-slot and flat-SAT placement);
- :class:`SegmentPlan` — one run of cascade stages and, for compacted tail
  segments, the survivor capacity entering the run plus the packed-tail
  backend chosen for that capacity;
- :class:`SlotLayout` — the flat slot / SAT layout over a bucket's
  pyramid levels: the index tables every packed program gathers through;
- :class:`CascadePlan` — the whole compiled plan for one (bucket, batch):
  levels + segments + layout, with a hashable ``key`` that *is* the
  jit-cache identity of the program built from it;
- :class:`LevelWavePlan` — the single-image per-level wave program's plan
  (dense window grid, per-compaction capacity ladder);
- :class:`StreamStatePlan` — the device stream step's geometry.

Everything here is derived data; :mod:`repro.plan.compiler` is the only
producer.  Executors (``Detector._build_level_fn``,
``Detector._build_batch_fn``, ``StreamEngine._build_stream_fn``) consume
these objects and derive nothing themselves.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

__all__ = ["LevelPlan", "SegmentPlan", "SlotLayout", "CascadePlan",
           "LevelWavePlan", "StreamStatePlan"]


class LevelPlan(NamedTuple):
    """Static geometry of one pyramid level inside a bucket's plan."""
    index: int          # position in the bucket's full pyramid plan
    height: int
    width: int
    scale: float        # original_size / level_size
    ny: int             # window-grid rows ((h - WINDOW) // step + 1)
    nx: int             # window-grid cols
    slot_offset: int    # first flat slot of this level in the layout

    @property
    def n_windows(self) -> int:
        return self.ny * self.nx

    @property
    def sat_size(self) -> int:
        return (self.height + 1) * (self.width + 1)

    @property
    def sat_stride(self) -> int:
        return self.width + 1


class SegmentPlan(NamedTuple):
    """A run of cascade stages ``[s0, s1)`` executed as one unit."""
    s0: int
    s1: int
    dense: bool         # dense full-grid wave vs compacted packed tail
    capacity: int = 0   # survivor capacity entering the segment (tail only)
    backend: str = ""   # packed-tail backend for that capacity (tail only;
    #                     the single-image wave tail runs on the dense grid
    #                     and carries no backend)

    @property
    def depth(self) -> int:
        """Cascade stages this segment evaluates per live lane."""
        return self.s1 - self.s0


class SlotLayout:
    """Flat slot / SAT layout over a bucket's pyramid levels: per slot its
    level and window origin, per level the base and row stride of its
    table in the flat SAT concatenation."""

    def __init__(self, levels: tuple[LevelPlan, ...], step: int):
        lvl_parts, y_parts, x_parts = [], [], []
        for lp in levels:
            gy = np.arange(lp.ny, dtype=np.int32) * step
            gx = np.arange(lp.nx, dtype=np.int32) * step
            lvl_parts.append(np.full(lp.n_windows, lp.index, np.int32))
            y_parts.append(np.repeat(gy, lp.nx))
            x_parts.append(np.tile(gx, lp.ny))
        self.lvl_of_slot = (np.concatenate(lvl_parts) if lvl_parts
                            else np.zeros(0, np.int32))
        self.y_of_slot = (np.concatenate(y_parts) if y_parts
                          else np.zeros(0, np.int32))
        self.x_of_slot = (np.concatenate(x_parts) if x_parts
                          else np.zeros(0, np.int32))
        self.n_slots = int(self.lvl_of_slot.shape[0])
        bases = np.cumsum([0] + [lp.sat_size for lp in levels])[:-1]
        self.sat_base_of_lvl = np.zeros(max(len(levels), 1), np.int32)
        self.sat_base_of_lvl[:len(levels)] = bases
        self.sat_stride_of_lvl = np.asarray(
            [lp.sat_stride for lp in levels], np.int32)


class CascadePlan:
    """One compiled plan: everything a packed cascade program needs.

    ``key`` is the hashable identity of the plan (and therefore of the
    program built from it) — two calls that compile the same key must
    reuse the same program.  ``levels`` are the bucket's pyramid levels;
    ``segments`` carry the per-segment capacities and tail backends;
    ``layout`` is the flat slot / SAT layout over the levels.
    """

    __slots__ = ("key", "hp", "wp", "batch", "step", "levels", "segments",
                 "capacities", "layout", "head_modes", "head_tile",
                 "lane_block")

    def __init__(self, key: tuple, hp: int, wp: int, batch: int, step: int,
                 levels: tuple[LevelPlan, ...],
                 segments: tuple[SegmentPlan, ...],
                 capacities: tuple[int, ...], layout: SlotLayout,
                 head_modes: tuple[str, ...] = (),
                 head_tile: tuple[int, ...] = (),
                 lane_block: tuple[int, ...] = ()):
        self.key = key
        self.hp, self.wp = hp, wp
        self.batch = batch
        self.step = step
        self.levels = levels
        self.segments = segments
        self.capacities = capacities
        self.layout = layout
        # per-active-level dense-head execution mode ("fused" megakernel vs
        # "split" three-dispatch path) plus the tuned tile shapes the
        # executors pass straight to the kernels; defaults mean "split with
        # package-default tiles" so pre-head-mode constructors stay valid
        self.head_modes = (head_modes if head_modes
                           else ("split",) * len(self.levels))
        self.head_tile = head_tile
        self.lane_block = lane_block

    @property
    def n_slots(self) -> int:
        """Flat slots of the layout: the pyramid's window count."""
        return self.layout.n_slots

    @property
    def n_windows_total(self) -> int:
        """Window count of the pyramid (== :attr:`n_slots`)."""
        return self.n_slots

    @property
    def work_units(self) -> int:
        """Modeled evaluation cost of the whole plan: lanes × stage depth
        summed over segments.  Dense segments sweep every slot of the batch
        for their stage run; a compacted tail segment evaluates at most its
        survivor ``capacity`` lanes per stage.  This is the cost weight the
        serving scheduler and energy governor shard and budget by — a deep
        tail costs more than its window count alone suggests, and two
        buckets of equal window count but different segmentation cost
        differently."""
        dense_lanes = self.n_slots * self.batch
        total = 0
        for seg in self.segments:
            lanes = dense_lanes if seg.dense else min(seg.capacity,
                                                      dense_lanes)
            total += lanes * seg.depth
        return max(total, 1)

    @property
    def dense_prefix(self) -> int:
        return sum(s.s1 - s.s0 for s in self.segments if s.dense)

    @property
    def tail_segments(self) -> tuple[SegmentPlan, ...]:
        return tuple(s for s in self.segments if not s.dense)

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, CascadePlan) and self.key == other.key

    def __repr__(self):
        return (f"CascadePlan(hp={self.hp}, wp={self.wp}, batch={self.batch},"
                f" levels={len(self.levels)},"
                f" n_slots={self.n_slots}, segments={self.segments})")


class LevelWavePlan(NamedTuple):
    """Plan of the single-image per-level wave program: dense window grid
    plus the per-compaction capacity ladder (fractions of *this* level's
    window count — the batched engine instead shares
    :attr:`CascadePlan.capacities` across the whole stack).  ``head_mode``
    is this level's dense-head execution choice ("fused" megakernel vs
    "split" three-dispatch path, from the measured crossover) and
    ``head_tile`` the tuned tile shape the executor hands the kernel
    (empty = package default)."""
    key: tuple
    height: int
    width: int
    step: int
    ny: int
    nx: int
    segments: tuple[SegmentPlan, ...]
    capacities: tuple[int, ...]
    head_mode: str = "split"
    head_tile: tuple = ()

    @property
    def n_windows(self) -> int:
        return self.ny * self.nx


class StreamStatePlan:
    """Compiler-owned geometry of the device-resident stream step.

    Everything the jitted ``plan_and_eval`` step (:meth:`repro.stream
    .StreamEngine.stream_step`) needs beyond a :class:`CascadePlan`:
    the tile grid covering the true (h, w) frame inside its (hp, wp)
    bucket, the per-level closed tile-range brackets of each window
    origin's receptive field (the range operands of
    :func:`repro.kernels.ref.changed_window_map_ref`, precomputed), the
    flat window-limit mask, the live-window count the full-refresh
    fraction is measured against, and the static capacity of the
    decoded-survivor slot list shipped back to host each frame.  ``key``
    is the plan's hashable identity — with the exactness flag and the
    refresh limits it keys the compiled step program.
    :func:`repro.plan.compile_stream_plan` is the only producer.
    """

    __slots__ = ("key", "hp", "wp", "h", "w", "tile", "halo", "ty", "tx",
                 "level_tile_ranges", "limit_mask", "n_live", "n_slots",
                 "decode_cap")

    def __init__(self, key: tuple, hp: int, wp: int, h: int, w: int,
                 tile: int, halo: int, ty: int, tx: int,
                 level_tile_ranges: tuple, limit_mask: np.ndarray,
                 n_live: int, n_slots: int, decode_cap: int):
        self.key = key
        self.hp, self.wp = hp, wp
        self.h, self.w = h, w
        self.tile, self.halo = tile, halo
        self.ty, self.tx = ty, tx
        # per level: (ty0, ty1, tx0, tx1) int32 closed tile-range brackets
        self.level_tile_ranges = level_tile_ranges
        self.limit_mask = limit_mask          # flat (n_slots,) bool
        self.n_live = n_live
        self.n_slots = n_slots
        self.decode_cap = decode_cap

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, StreamStatePlan) and self.key == other.key

    def __repr__(self):
        return (f"StreamStatePlan(hp={self.hp}, wp={self.wp}, h={self.h}, "
                f"w={self.w}, tile={self.tile}, halo={self.halo}, "
                f"grid=({self.ty}, {self.tx}), n_slots={self.n_slots}, "
                f"n_live={self.n_live}, decode_cap={self.decode_cap})")
