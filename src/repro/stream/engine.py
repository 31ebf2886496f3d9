"""Packed incremental cascade evaluation over changed windows.

This is ``Detector._build_batch_fn``'s shared-compaction tail with the
dense-wave head cut off: the initial alive set is not "every window that
survived the dense waves" but "every window whose tile content changed"
(computed on host by :mod:`repro.stream.tiles`).  Changed windows from
every frame in the stack and every pyramid level are compacted into one
shared window list and run through *all* cascade stages by the shared
packed-tail evaluator (:mod:`repro.kernels.packed_tail`) — whose three
backends (gather oracle, bulk gather, blocked Pallas kernel) are
bit-identical per window to the baseline engine's tail, so a recomputed
window reaches exactly the decision a full-frame ``detect`` would.

One jitted program per :class:`repro.plan.CascadePlan` — the plan layer
compiles (bucket shape, batch size, capacity rung, active level subset)
into the typed IR this executor consumes: the rung is the smallest
power-of-two holding the flush's actual changed count
(:func:`repro.plan.stream_capacity_rung`; the host built the masks, so
the count is known before dispatch), the *level subset* is the set of
pyramid levels that actually have changed windows this flush, and the
rung's packed-tail backend is the plan's per-segment decision off the
measured ``EngineConfig.tail_rungs`` crossover ladder.  Levels whose
windows are all cached are skipped entirely — no SAT is built for them,
and the packed flat slot/SAT layout covers only the active subset.
Concurrent streams' changed-tile work items share the single compaction,
which is what makes many mostly-static streams cheap: the packed list is
sized to the *sum* of their (small) changed sets, paid once per flush.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.cascade import Cascade, WINDOW
from repro.core.engine import Detector
from repro.core.integral import integral_images
from repro.core.pyramid import downscale_indices
from repro.kernels import packed_tail
from repro.kernels.tile_change import (tile_change_mask_kernel,
                                       changed_window_map_kernel)
from repro.plan import (STREAM_CAP_BASE, LevelSubset,  # noqa: F401
                        StreamGeometry, compile_plan, compile_stream_plan,
                        stream_budget, stream_capacity_rung)

__all__ = ["StreamGeometry", "StreamEngine", "LevelSubset", "StreamState",
           "StreamStepOut"]

_AREA = float(WINDOW * WINDOW)


def _packed_inv_sigma(pair_flat: jax.Array, img: jax.Array, base: jax.Array,
                      stride: jax.Array, ys: jax.Array, xs: jax.Array
                      ) -> jax.Array:
    """1/sigma for packed windows living on different images and levels.

    ``pair_flat`` is (B, 2, sum_l (h_l+1)*(w_l+1)) — the stacked
    (ii2, iic) pair of every level, flattened and concatenated.  Same
    corner order and variance identity as
    :func:`repro.core.integral.window_inv_sigma`, bit-for-bit, only the
    lookup goes through the packed (img, base + y*stride + x) indexing —
    dense per-grid normalization would be wasted work when only a small
    changed subset of windows is evaluated.
    """

    def rect(tab, y0, x0):
        y1, x1 = y0 + WINDOW, x0 + WINDOW
        return (pair_flat[img, tab, base + y1 * stride + x1]
                - pair_flat[img, tab, base + y0 * stride + x1]
                - pair_flat[img, tab, base + y1 * stride + x0]
                + pair_flat[img, tab, base + y0 * stride + x0])

    s2 = rect(0, ys, xs)
    s1 = rect(1, ys, xs)
    var = s2 / _AREA - (s1 / _AREA) ** 2
    sigma = jnp.sqrt(jnp.maximum(var, 1.0))
    return 1.0 / sigma


class StreamState(NamedTuple):
    """One stream's device-resident temporal state (a donated pytree).

    Every field is a jax array that lives on device across frames and is
    *donated* through the jitted plan-and-eval step, so steady-state
    frames reuse the same buffers — the only per-frame host->device
    transfer is the new frame, and the only device->host transfer is the
    :class:`StreamStepOut` scalars plus the decoded survivor slot list.
    """
    ref: jax.Array        # (hp, wp) f32 reference pixels, zero-padded
    bitmap: jax.Array     # (n_slots,) bool cached survivor decisions
    drift: jax.Array      # (ty, tx) f32 peak change score of tiles whose
    #                       cached decisions were *not* refreshed (pure
    #                       diagnostic: scoring is always vs the reference
    #                       frame, so sub-threshold drift never compounds)
    frame_idx: jax.Array  # () i32 stream frame counter
    last_full: jax.Array  # () i32 frame index of the last full refresh


class StreamStepOut(NamedTuple):
    """Per-frame result of the device plan-and-eval step (device arrays;
    the host fetches the scalars, and the slot list only on incremental
    commits)."""
    mode: jax.Array           # () i32: 0 cached, 1 incremental, 2 full
    tiles_changed: jax.Array  # () i32 changed tiles after halo dilation
    n_rec: jax.Array          # () i32 windows to recompute
    levels_active: jax.Array  # () i32 levels with any changed window
    retry: jax.Array          # () bool: packed rung overflow — nothing
    #                           committed; re-dispatch at a larger rung
    n_surv: jax.Array         # () i32 survivors in the committed bitmap
    slots: jax.Array          # (decode_cap,) i32 ascending survivor slots
    #                           (fill value n_slots past n_surv)


class StreamEngine:
    """Jitted incremental evaluators over a :class:`Detector`'s cascade."""

    def __init__(self, detector: Detector, max_changed_frac: float = 0.5):
        self.detector = detector
        self.max_changed_frac = max_changed_frac
        self._geos: dict[tuple[int, int], StreamGeometry] = {}
        self._fns: dict[tuple, object] = {}
        # head-work accounting: how many per-level SAT builds the subset
        # programs actually ran vs the all-level layout's total (tests and
        # benchmarks assert fully-cached levels build no SAT from these)
        self.sat_level_builds = 0
        self.sat_level_total = 0
        self.dispatches = 0
        self.program_builds = 0          # executor builds (plan-cache probe)

    @property
    def sat_level_frac(self) -> float:
        """Fraction of pyramid levels whose SAT was built, over all
        incremental dispatches (1.0 = the old all-level behaviour)."""
        return self.sat_level_builds / max(self.sat_level_total, 1)

    def geometry(self, hp: int, wp: int) -> StreamGeometry:
        key = (hp, wp)
        if key not in self._geos:
            self._geos[key] = StreamGeometry(self.detector, hp, wp)
        return self._geos[key]

    def cap_budget(self, geo: StreamGeometry, batch: int) -> int:
        """Most changed windows a flush may evaluate incrementally; beyond
        it a full refresh is cheaper anyway (the caller's fallback)."""
        return stream_budget(geo.n_slots, batch, self.max_changed_frac)

    def _cap_for(self, n_sub_slots: int, batch: int, n_changed: int) -> int:
        """Smallest ladder rung holding ``n_changed`` packed windows, capped
        at the active subset's own slot count (the plan layer's ladder)."""
        return stream_capacity_rung(n_sub_slots, batch, n_changed)

    # ------------------------------------------------------------- build
    def _build_fn(self, plan):
        """Thin executor over a stream-shaped :class:`repro.plan
        .CascadePlan`: SATs are built (and the flat slot layout laid out)
        over only the plan's active levels — fully cached levels cost
        nothing, not even their SAT pass.  The whole incremental tail is
        the plan's single all-stage segment; its capacity is the rung and
        its backend is the plan's decision off the crossover ladder."""
        det = self.detector
        hp, wp = plan.hp, plan.wp
        batch = plan.batch
        seg = plan.segments[0]
        cap, backend = seg.capacity, seg.backend
        n_slots = plan.n_slots
        cascade_static = det.cascade
        self.program_builds += 1
        layout = plan.layout
        lvl_of_slot = jnp.asarray(layout.lvl_of_slot)
        y_of_slot = jnp.asarray(layout.y_of_slot)
        x_of_slot = jnp.asarray(layout.x_of_slot)
        sat_base_of_lvl = jnp.asarray(layout.sat_base_of_lvl)
        sat_stride_of_lvl = jnp.asarray(layout.sat_stride_of_lvl)

        def frame_fn(cascade: Cascade, stack: jax.Array,
                     mask_flat: jax.Array):
            # stack: (B, hp, wp) f32 frames; mask_flat: (B, n_slots) bool of
            # windows to recompute (already limit-masked on host), laid out
            # over the active subset's slots only.
            sat_parts, pair_parts = [], []
            for lp in plan.levels:
                ys_idx = downscale_indices(hp, lp.height)
                xs_idx = downscale_indices(wp, lp.width)
                img_l = stack[:, ys_idx[:, None], xs_idx[None, :]]
                ii_l, pair_l = jax.vmap(integral_images)(img_l)
                sat_parts.append(ii_l.reshape(batch, -1))
                pair_parts.append(pair_l.reshape(batch, 2, -1))

            alive_flat = mask_flat.reshape(-1)
            ii_flat = jnp.concatenate(sat_parts, axis=1)
            pair_flat = jnp.concatenate(pair_parts, axis=2)
            recomputed = mask_flat.sum(axis=1).astype(jnp.int32)  # (B,)
            overflow = alive_flat.sum() > cap
            idx = jnp.nonzero(alive_flat, size=cap, fill_value=-1)[0]
            sel = jnp.maximum(idx, 0)
            valid = idx >= 0
            b_sel = sel // n_slots
            slot = sel % n_slots
            lvl_sel = jnp.take(lvl_of_slot, slot)
            y_sel = jnp.take(y_of_slot, slot)
            x_sel = jnp.take(x_of_slot, slot)
            base_sel = jnp.take(sat_base_of_lvl, lvl_sel)
            stride_sel = jnp.take(sat_stride_of_lvl, lvl_sel)
            inv_sel = _packed_inv_sigma(pair_flat, b_sel, base_sel,
                                        stride_sel, y_sel, x_sel)
            ss_run = packed_tail.stage_sums(
                cascade, cascade_static, seg.s0, seg.s1, ii_flat, b_sel,
                base_sel, stride_sel, y_sel, x_sel, inv_sel,
                backend=backend, tile=plan.lane_block)
            for j, s in enumerate(range(seg.s0, seg.s1)):
                valid = valid & (ss_run[j] >= cascade.stage_threshold[s])
            # scatter survivors back onto the full (B, n_slots) grid; dead
            # and padding lanes target index B*n_slots which is dropped
            target = jnp.where(valid, sel, batch * n_slots)
            survivors = jnp.zeros(batch * n_slots, bool).at[target].set(
                True, mode="drop")
            return survivors.reshape(batch, n_slots), recomputed, overflow

        return jax.jit(frame_fn)

    def _fn(self, hp: int, wp: int, batch: int, cap: int,
            levels: tuple[int, ...]):
        det = self.detector
        plan = compile_plan(det.config, det.n_stages, hp, wp, batch=batch,
                            levels=levels, capacity=cap)
        if plan.key not in self._fns:
            self._fns[plan.key] = self._build_fn(plan)
        return self._fns[plan.key]

    # ----------------------------------------------- device-resident state
    def stream_plan(self, hp: int, wp: int, h: int, w: int, tile: int,
                    halo: int, decode_cap: int | None = None):
        """The compiled :class:`repro.plan.StreamStatePlan` for one
        (bucket, true frame shape, tile, halo)."""
        det = self.detector
        return compile_stream_plan(det.config, det.n_stages, hp, wp, h, w,
                                   tile, halo, decode_cap=decode_cap)

    def init_state(self, splan, frame: np.ndarray, bitmap: np.ndarray,
                   frame_idx: int, last_full: int) -> StreamState:
        """Upload a stream's temporal state (after a host full refresh)."""
        ref = np.zeros((splan.hp, splan.wp), np.float32)
        ref[:splan.h, :splan.w] = frame
        # repro: ignore[HOST_SYNC] keyframe upload: host bitmap seeds the device state
        bm = np.asarray(bitmap, bool)
        return StreamState(jnp.asarray(ref), jnp.asarray(bm),
                           jnp.zeros((splan.ty, splan.tx), jnp.float32),
                           jnp.asarray(np.int32(frame_idx)),
                           jnp.asarray(np.int32(last_full)))

    def refresh_state(self, splan):
        """The fast-path twin of :meth:`init_state` for device streams
        whose full-refresh frame is already device-resident (it was the
        step's input): donates the stale state and the frame buffer, so
        the only host→device traffic is the survivor bitmap and two
        counters."""
        key = ("stream_refresh", splan.key)
        if key not in self._fns:
            ty, tx = splan.ty, splan.tx
            self.program_builds += 1

            def refresh(state: StreamState, frame: jax.Array,
                        bitmap: jax.Array, frame_idx: jax.Array,
                        last_full: jax.Array) -> StreamState:
                del state    # donated: its buffers back the new pytree
                return StreamState(frame, bitmap,
                                   jnp.zeros((ty, tx), jnp.float32),
                                   frame_idx, last_full)

            self._fns[key] = jax.jit(refresh, donate_argnums=(0, 1))
        return self._fns[key]

    def provisional_refresh(self, splan):
        """Re-seed only the verdict-bearing half of the state — reference
        pixels and counters — leaving the survivor bitmap stale.  The
        step's mode decision never reads the bitmap, so a successor frame
        can dispatch against this *before* the full refresh's host detect
        produces the real bitmap; a committed verdict is then re-run
        against the trued-up state (see ``VideoDetector.poll``)."""
        key = ("stream_refresh_prov", splan.key)
        if key not in self._fns:
            ty, tx = splan.ty, splan.tx
            self.program_builds += 1

            def refresh(state: StreamState, frame: jax.Array,
                        frame_idx: jax.Array, last_full: jax.Array
                        ) -> StreamState:
                return StreamState(frame, state.bitmap,
                                   jnp.zeros((ty, tx), jnp.float32),
                                   frame_idx, last_full)

            self._fns[key] = jax.jit(refresh, donate_argnums=(0, 1))
        return self._fns[key]

    def stream_step(self, splan, rung: int, exact: bool,
                    full_refresh_frac: float):
        """The jitted donated plan-and-eval step for (plan, rung, exact,
        refresh policy) — cached like every other program."""
        # the host float compares `n > frac * total` are reproduced on
        # device as integer compares against floor(frac * total): for
        # integer n and real c >= 0, n > c iff n > floor(c)
        tile_lim = int(full_refresh_frac * (splan.ty * splan.tx))
        win_lim = int(full_refresh_frac * max(splan.n_live, 1))
        budget = stream_budget(splan.n_slots, 1, self.max_changed_frac)
        key = ("stream_state", splan.key, rung, exact, tile_lim, win_lim,
               budget)
        if key not in self._fns:
            self._fns[key] = self._build_stream_fn(
                splan, rung, exact, tile_lim, win_lim, budget)
        return self._fns[key]

    def _build_stream_fn(self, splan, rung: int, exact: bool, tile_lim: int,
                         win_lim: int, budget: int):
        """One fused jitted program per (stream plan, rung, exactness,
        refresh limits): on-device tile change scoring, per-level window
        mapping, the cached/incremental/full mode decision, and — only
        when an incremental commit is on (``lax.cond``) — the per-level
        SATs plus the packed all-stage tail at the fixed ``rung``
        capacity.  The state argument is donated: steady-state frames
        allocate nothing new."""
        det = self.detector
        hp, wp, h, w = splan.hp, splan.wp, splan.h, splan.w
        tile, halo = splan.tile, splan.halo
        plan = compile_plan(det.config, det.n_stages, hp, wp, batch=1,
                            capacity=rung)
        seg = plan.segments[0]
        cap, backend = seg.capacity, seg.backend
        n_slots = plan.n_slots
        cascade_static = det.cascade
        self.program_builds += 1
        layout = plan.layout
        lvl_of_slot = jnp.asarray(layout.lvl_of_slot)
        y_of_slot = jnp.asarray(layout.y_of_slot)
        x_of_slot = jnp.asarray(layout.x_of_slot)
        sat_base_of_lvl = jnp.asarray(layout.sat_base_of_lvl)
        sat_stride_of_lvl = jnp.asarray(layout.sat_stride_of_lvl)
        ranges = [tuple(jnp.asarray(a) for a in r)
                  for r in splan.level_tile_ranges]
        offs = [0]
        for lp in plan.levels:
            offs.append(offs[-1] + lp.n_windows)
        valid_parts = [jnp.asarray(splan.limit_mask[offs[li]:offs[li + 1]])
                       for li in range(len(plan.levels))]
        decode_cap = splan.decode_cap

        def step(cascade: Cascade, state: StreamState, frame: jax.Array,
                 threshold: jax.Array, kf_interval: jax.Array
                 ) -> tuple[StreamState, StreamStepOut]:
            # frame: (hp, wp) f32, zero-padded like the reference
            changed, scores = tile_change_mask_kernel(
                state.ref[:h, :w], frame[:h, :w], threshold, tile=tile,
                halo=halo, exact=exact)
            n_tiles = changed.sum().astype(jnp.int32)

            def build_maps():
                mask_parts = [changed_window_map_kernel(changed, ty0, ty1,
                                                        tx0, tx1, valid)
                              for (ty0, ty1, tx0, tx1), valid
                              in zip(ranges, valid_parts)]
                return (jnp.concatenate(mask_parts),
                        jnp.stack([m.any() for m in mask_parts]))

            def skip_maps():
                # the tile count alone already forces a full refresh: the
                # per-level maps would never be read (n_rec/levels_active
                # report 0; host stats for full frames use constants)
                return (jnp.zeros(offs[-1], bool),
                        jnp.zeros(len(plan.levels), bool))

            mask_flat, lvl_any = jax.lax.cond(n_tiles <= tile_lim,
                                              build_maps, skip_maps)
            n_rec = mask_flat.sum().astype(jnp.int32)
            levels_active = lvl_any.astype(jnp.int32).sum()

            due = (kf_interval > 0) & (state.frame_idx - state.last_full
                                       >= kf_interval)
            full_needed = (due | (n_tiles > tile_lim) | (n_rec > win_lim)
                           | (n_rec > budget))
            retry = (n_rec > cap) & ~full_needed
            commit = ~full_needed & ~retry
            mode = jnp.where(full_needed, 2,
                             jnp.where(n_tiles > 0, 1, 0)).astype(jnp.int32)

            def eval_tail() -> jax.Array:
                sat_parts, pair_parts = [], []
                for li, lp in enumerate(plan.levels):
                    ys_idx = downscale_indices(hp, lp.height)
                    xs_idx = downscale_indices(wp, lp.width)

                    def build(ys_idx=ys_idx, xs_idx=xs_idx):
                        img_l = frame[ys_idx[:, None], xs_idx[None, :]]
                        ii_l, pair_l = integral_images(img_l)
                        return ii_l.reshape(-1), pair_l.reshape(2, -1)

                    def skip(lp=lp):
                        return (jnp.zeros(lp.sat_size, jnp.float32),
                                jnp.zeros((2, lp.sat_size), jnp.float32))

                    # fully-cached levels build no SAT, like the host
                    # subset programs — but resolved on device, per frame
                    ii_l, pair_l = jax.lax.cond(lvl_any[li], build, skip)
                    sat_parts.append(ii_l)
                    pair_parts.append(pair_l)
                ii_flat = jnp.concatenate(sat_parts)[None, :]
                pair_flat = jnp.concatenate(pair_parts, axis=1)[None]
                idx = jnp.nonzero(mask_flat, size=cap, fill_value=-1)[0]
                sel = jnp.maximum(idx, 0)
                valid = idx >= 0
                b_sel = jnp.zeros_like(sel)
                lvl_sel = jnp.take(lvl_of_slot, sel)
                y_sel = jnp.take(y_of_slot, sel)
                x_sel = jnp.take(x_of_slot, sel)
                base_sel = jnp.take(sat_base_of_lvl, lvl_sel)
                stride_sel = jnp.take(sat_stride_of_lvl, lvl_sel)
                inv_sel = _packed_inv_sigma(pair_flat, b_sel, base_sel,
                                            stride_sel, y_sel, x_sel)
                ss_run = packed_tail.stage_sums(
                    cascade, cascade_static, seg.s0, seg.s1, ii_flat,
                    b_sel, base_sel, stride_sel, y_sel, x_sel, inv_sel,
                    backend=backend, tile=plan.lane_block)
                for j, s in enumerate(range(seg.s0, seg.s1)):
                    valid = valid & (ss_run[j] >= cascade.stage_threshold[s])
                target = jnp.where(valid, sel, n_slots)
                return jnp.zeros(n_slots, bool).at[target].set(
                    True, mode="drop")

            def commit_step():
                survivors = jax.lax.cond(
                    n_rec > 0, eval_tail, lambda: jnp.zeros(n_slots, bool))
                new_bitmap = (state.bitmap & ~mask_flat) | survivors
                pix = jnp.repeat(jnp.repeat(changed, tile, axis=0),
                                 tile, axis=1)[:h, :w]
                pix = jnp.pad(pix, ((0, hp - h), (0, wp - w)))
                new_ref = jnp.where(pix, frame, state.ref)
                new_drift = jnp.where(changed, 0.0,
                                      jnp.maximum(state.drift, scores))
                slots = jnp.nonzero(new_bitmap, size=decode_cap,
                                    fill_value=n_slots)[0].astype(jnp.int32)
                n_surv = new_bitmap.sum().astype(jnp.int32)
                return new_ref, new_bitmap, new_drift, slots, n_surv

            def skip_step():
                # full/retry verdict: nothing commits — the state passes
                # through untouched and the decode outputs are never read
                return (state.ref, state.bitmap, state.drift,
                        jnp.full(decode_cap, n_slots, jnp.int32),
                        jnp.zeros((), jnp.int32))

            new_ref, new_bitmap, new_drift, slots, n_surv = jax.lax.cond(
                commit, commit_step, skip_step)
            new_fi = state.frame_idx + commit.astype(jnp.int32)
            out = StreamStepOut(mode, n_tiles, n_rec, levels_active, retry,
                                n_surv, slots)
            return StreamState(new_ref, new_bitmap, new_drift, new_fi,
                               state.last_full), out

        return jax.jit(step, donate_argnums=(1,))

    # -------------------------------------------------------------- run
    def incremental(self, frames: list[np.ndarray],
                    masks_per_frame: list[list[np.ndarray]],
                    hp: int, wp: int,
                    active: tuple[int, ...] | None = None
                    ) -> tuple[list[np.ndarray], np.ndarray, bool]:
        """Evaluate changed windows of a same-bucket stack of frames.

        ``masks_per_frame[i]`` is one flat bool mask per pyramid level for
        frame ``i``.  The dispatch compiles (and runs) a *level-subset*
        program keyed on the plan for the set of levels with any changed
        window across the stack; ``active`` optionally widens that set
        (e.g. the serving layer passes the union of its sessions'
        ``FramePlan.active_levels`` so one chunk shares one program).
        Returns ``(survivor bitmaps per frame (flat n_slots),
        recomputed-window counts, overflow)`` — on overflow (more changed
        windows than ``cap_budget``) nothing is dispatched and the caller
        must fall back to a full refresh.
        """
        geo = self.geometry(hp, wp)
        batch = len(frames)
        n_levels = len(geo.plan)
        mask_flat = np.stack([np.concatenate(masks_per_frame[i])
                              for i in range(batch)])
        counts = mask_flat.sum(axis=1).astype(np.int32)
        n_changed = int(counts.sum())
        if n_changed > self.cap_budget(geo, batch):
            return [], counts, True
        # active level subset = union over the stack of levels with any
        # changed window (plus the caller's widening hint)
        changed_lv = {li for li in range(n_levels)
                      if mask_flat[:, geo.slot_offsets[li]:
                                   geo.slot_offsets[li + 1]].any()}
        if active is not None:
            changed_lv |= set(active)
        levels = tuple(sorted(changed_lv))
        self.dispatches += 1
        self.sat_level_builds += len(levels)
        self.sat_level_total += n_levels
        if not levels:          # nothing changed anywhere: no program at all
            return ([np.zeros(geo.n_slots, bool) for _ in range(batch)],
                    counts, False)
        sub = geo.subset(levels)
        mask_sub = mask_flat[:, sub.slot_indices]
        cap = self._cap_for(sub.n_slots, batch, n_changed)
        stack = np.zeros((batch, hp, wp), np.float32)
        for i, f in enumerate(frames):
            h, w = f.shape
            stack[i, :h, :w] = f
        out, recomputed, overflow = self._fn(hp, wp, batch, cap, levels)(
            self.detector.cascade, jnp.asarray(stack),
            jnp.asarray(mask_sub))
        # repro: ignore[HOST_SYNC] host-path contract: the host-resident caches merge survivor bitmaps here (the device-resident path avoids this sync)
        sub_bitmaps = np.asarray(out)
        bitmaps = []
        for i in range(batch):  # scatter subset survivors into full layout
            full = np.zeros(geo.n_slots, bool)
            full[sub.slot_indices] = sub_bitmaps[i]
            bitmaps.append(full)
        # repro: ignore[HOST_SYNC] host-path contract: recompute counts and the overflow flag gate the caller's full-refresh fallback
        return (bitmaps, np.asarray(recomputed), bool(np.asarray(overflow)))
