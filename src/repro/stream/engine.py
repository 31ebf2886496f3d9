"""The device-resident stream step: incremental cascade evaluation over
changed windows.

:meth:`StreamEngine.stream_step` builds one jitted step per (stream plan,
exactness, refresh limits).  It scores the frame's tiles against the
reference, maps changed tiles to changed windows per pyramid level,
decides cached / incremental / full, and evaluates every window to
recompute — the changed windows, or every live window on a keyframe or a
full refresh — through one head call per level whose initial alive mask
is that level's map: the masked fused head
(:func:`repro.kernels.ops.fused_head` with ``live``), whose tiles without
a live window stop after one mask check.  There is no packed tail, no
compacted window list, so no capacity and no rung: the program's shape
does not depend on how much changed, and nothing gathers SAT corners.
Levels with nothing to evaluate are skipped by ``lax.cond``.  Survivors
merge into the donated state's bitmap (``(bitmap & ~recomputed) |
survivors``).

It reads int32 summed-area tables (:mod:`repro.core.integral`), so a
window's decision depends on the pixels under it alone and a cached
decision is exact at threshold 0.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.cascade import Cascade, WINDOW
from repro.core.engine import Detector
from repro.core.features import run_sums_grid
from repro.core.integral import integral_images, window_inv_sigma_grid
from repro.core.pyramid import downscale_indices
from repro.kernels.autotune import DEFAULT_TILE
from repro.kernels.tile_change import (tile_change_mask_kernel,
                                       changed_window_map_kernel)
from repro.plan import StreamGeometry, compile_plan, compile_stream_plan

__all__ = ["StreamGeometry", "StreamEngine", "StreamState", "StreamStepOut"]


class StreamState(NamedTuple):
    """One stream's device-resident temporal state (a donated pytree).

    Every field is a jax array that lives on device across frames and is
    *donated* through the jitted plan-and-eval step, so steady-state
    frames reuse the same buffers — the only per-frame host->device
    transfer is the new frame, and the only device->host transfer is the
    :class:`StreamStepOut` scalars plus the decoded survivor slot list.

    ``slots`` is ``bitmap``'s ascending survivor-slot list, carried so
    that a step whose committed bitmap equals the one it was given (a
    cached frame, or evaluated windows that kept their verdicts) reuses it
    instead of compacting all ``n_slots`` again.
    """
    ref: jax.Array        # (hp, wp) f32 reference pixels, zero-padded
    bitmap: jax.Array     # (n_slots,) bool cached survivor decisions
    slots: jax.Array      # (decode_cap,) i32 ascending survivor slots of
    #                       bitmap (fill value n_slots past its count)
    drift: jax.Array      # (ty, tx) f32 peak change score of tiles whose
    #                       cached decisions were *not* refreshed (pure
    #                       diagnostic: scoring is always vs the reference
    #                       frame, so sub-threshold drift never compounds)
    frame_idx: jax.Array  # () i32 stream frame counter
    last_full: jax.Array  # () i32 frame index of the last full refresh;
    #                       -1 before the stream's first frame


class StreamStepOut(NamedTuple):
    """Per-frame result of the device plan-and-eval step (device arrays;
    the host fetches the scalars, then the slot list).  ``slots`` is the
    committed bitmap's list on every frame; ``slots_built`` says whether
    this step compacted it anew (1, the bitmap changed) or carried the
    state's list (0)."""
    mode: jax.Array           # () i32: 0 cached, 1 incremental, 2 full
    tiles_changed: jax.Array  # () i32 changed tiles after halo dilation
    n_rec: jax.Array          # () i32 windows to recompute
    levels_active: jax.Array  # () i32 levels with any changed window
    n_surv: jax.Array         # () i32 survivors in the committed bitmap
    slots: jax.Array          # (decode_cap,) i32 ascending survivor slots
    #                           (fill value n_slots past n_surv)
    slots_built: jax.Array    # () i32 1 if the step rebuilt slots, else 0
    head_tiles: jax.Array     # (n_stages,) i32 head tiles that ran each
    #                           stage, summed over the levels evaluated
    dense_tiles: jax.Array    # () i32 head tiles of the levels evaluated


class StreamEngine:
    """The jitted device stream steps over a :class:`Detector`'s cascade,
    one per (stream plan, exactness, refresh limits)."""

    def __init__(self, detector: Detector):
        self.detector = detector
        self._geos: dict[tuple[int, int], StreamGeometry] = {}
        self._fns: dict[tuple, object] = {}
        self.program_builds = 0          # executor builds (plan-cache probe)
        self.head_tile = tuple(DEFAULT_TILE)   # the device step's head tile
        # weak classifiers per stage: the head-work counters' unit
        # repro: ignore[HOST_SYNC] host constant of the cascade, read once at construction
        self.n_weak = np.diff(np.asarray(detector.cascade.stage_offsets))

    def geometry(self, hp: int, wp: int) -> StreamGeometry:
        key = (hp, wp)
        if key not in self._geos:
            self._geos[key] = StreamGeometry(self.detector, hp, wp)
        return self._geos[key]

    # ----------------------------------------------- device-resident state
    def stream_plan(self, hp: int, wp: int, h: int, w: int, tile: int,
                    halo: int, decode_cap: int | None = None):
        """The compiled :class:`repro.plan.StreamStatePlan` for one
        (bucket, true frame shape, tile, halo)."""
        det = self.detector
        return compile_stream_plan(det.config, det.n_stages, hp, wp, h, w,
                                   tile, halo, decode_cap=decode_cap)

    def init_state(self, splan, frame_idx: int) -> StreamState:
        """A fresh device state: no reference, no cached decision (the
        all-false bitmap, whose slot list is all fill), and ``last_full``
        -1, so the stream's next step runs a full refresh (the stream's
        opening keyframe) on the device."""
        return StreamState(jnp.zeros((splan.hp, splan.wp), jnp.float32),
                           jnp.zeros(splan.n_slots, bool),
                           jnp.full(splan.decode_cap, splan.n_slots,
                                    jnp.int32),
                           jnp.zeros((splan.ty, splan.tx), jnp.float32),
                           jnp.asarray(np.int32(frame_idx)),
                           jnp.asarray(np.int32(-1)))

    def stream_step(self, splan, exact: bool, full_refresh_frac: float):
        """The jitted donated plan-and-eval step for (plan, exactness,
        refresh policy) — one program, cached like every other."""
        # the host float compares `n > frac * total` are reproduced on
        # device as integer compares against floor(frac * total): for
        # integer n and real c >= 0, n > c iff n > floor(c)
        tile_lim = int(full_refresh_frac * (splan.ty * splan.tx))
        win_lim = int(full_refresh_frac * max(splan.n_live, 1))
        key = ("stream_state", splan.key, exact, tile_lim, win_lim)
        if key not in self._fns:
            self._fns[key] = self._build_stream_fn(splan, exact, tile_lim,
                                                   win_lim)
        return self._fns[key]

    def _build_stream_fn(self, splan, exact: bool, tile_lim: int,
                         win_lim: int):
        """One fused jitted program per (stream plan, exactness, refresh
        limits): on-device tile change scoring, per-level window mapping,
        the cached/incremental/full mode decision, and the commit.  Each
        level with a window to evaluate runs the whole cascade through one
        head call whose initial alive mask is that level's recompute map —
        the changed windows, or every live window on a keyframe or full
        refresh — so the frame's work follows what changed with no
        compacted list, no capacity and no gather of SAT corners.  The
        head is the masked fused kernel with its tile exit where the
        kernels run (``use_pallas``, step 1), else the jnp dense sums.
        Levels with nothing to evaluate are skipped (``lax.cond``).  The
        survivor-slot list (``jnp.nonzero`` over all slots) is rebuilt only
        when the committed bitmap differs from the state's; otherwise the
        state's list is carried (``slots_built`` 0).  The state argument
        is donated: steady-state frames allocate nothing new."""
        det = self.detector
        hp, wp, h, w = splan.hp, splan.wp, splan.h, splan.w
        tile, halo = splan.tile, splan.halo
        plan = compile_plan(det.config, det.n_stages, hp, wp, batch=1)
        n_stages = det.n_stages
        step_px = plan.step
        n_slots = plan.n_slots
        cascade_static = det.cascade
        fused = bool(det.config.use_pallas) and step_px == 1
        head_tile = self.head_tile = tuple(plan.head_tile or DEFAULT_TILE)
        hty, htx = head_tile
        if fused:
            from repro.kernels import ops as kops
        self.program_builds += 1
        ranges = [tuple(jnp.asarray(a) for a in r)
                  for r in splan.level_tile_ranges]
        offs = [0]
        for lp in plan.levels:
            offs.append(offs[-1] + lp.n_windows)
        valid_np = [splan.limit_mask[offs[li]:offs[li + 1]]
                    for li in range(len(plan.levels))]
        valid_parts = [jnp.asarray(v) for v in valid_np]
        # levels with a live window: what a full refresh evaluates
        full_levels = jnp.asarray([bool(v.any()) for v in valid_np])
        level_tiles = [-(-lp.ny // hty) * -(-lp.nx // htx)
                       for lp in plan.levels]
        decode_cap = splan.decode_cap

        def level_head(cascade, frame, li, live):
            """Survivors of level ``li`` among its ``live`` windows, and
            the head tiles that ran each stage."""
            lp = plan.levels[li]
            ys_idx = downscale_indices(hp, lp.height)
            xs_idx = downscale_indices(wp, lp.width)
            img_l = frame[ys_idx[:, None], xs_idx[None, :]]
            live = live.reshape(lp.ny, lp.nx)
            if fused:
                _ii, _inv, sums = kops.fused_head(
                    cascade, cascade_static, 0, n_stages, img_l,
                    tile=head_tile, live=live)
                # a tile's origin reads -inf at each stage it skipped
                tiles = (sums[:, ::hty, ::htx] > -jnp.inf).sum(
                    axis=(1, 2)).astype(jnp.int32)
            else:
                ii, pair = integral_images(img_l)
                inv = window_inv_sigma_grid(pair, lp.ny, lp.nx, step_px,
                                            WINDOW)
                sums = run_sums_grid(cascade, ii, inv, step_px, 0, n_stages)
                tiles = jnp.full(n_stages, level_tiles[li], jnp.int32)
            alive = live
            for s in range(n_stages):
                alive = alive & (sums[s] >= cascade.stage_threshold[s])
            return alive.reshape(-1), tiles

        def step(cascade: Cascade, state: StreamState, frame: jax.Array,
                 threshold: jax.Array, kf_interval: jax.Array
                 ) -> tuple[StreamState, StreamStepOut]:
            # frame: (hp, wp) f32, zero-padded like the reference
            changed, scores = tile_change_mask_kernel(
                state.ref[:h, :w], frame[:h, :w], threshold, tile=tile,
                halo=halo, exact=exact)
            n_tiles = changed.sum().astype(jnp.int32)

            def build_maps():
                return [changed_window_map_kernel(changed, ty0, ty1, tx0,
                                                  tx1, valid)
                        for (ty0, ty1, tx0, tx1), valid
                        in zip(ranges, valid_parts)]

            def skip_maps():
                # nothing changed, or the tile count alone already forces
                # a full refresh: the maps would be all-false or unread
                return [jnp.zeros(v.shape, bool) for v in valid_parts]

            mask_parts = jax.lax.cond((n_tiles > 0) & (n_tiles <= tile_lim),
                                      build_maps, skip_maps)
            mask_flat = jnp.concatenate(mask_parts)
            lvl_any = jnp.stack([m.any() for m in mask_parts])
            n_rec = mask_flat.sum().astype(jnp.int32)
            levels_active = lvl_any.astype(jnp.int32).sum()

            due = (state.last_full < 0) | (
                (kf_interval > 0)
                & (state.frame_idx - state.last_full >= kf_interval))
            full = due | (n_tiles > tile_lim) | (n_rec > win_lim)
            mode = jnp.where(full, 2,
                             jnp.where(n_tiles > 0, 1, 0)).astype(jnp.int32)
            run = jnp.where(full, full_levels, lvl_any)

            surv_parts = []
            head_tiles = jnp.zeros(n_stages, jnp.int32)
            dense_tiles = jnp.zeros((), jnp.int32)
            for li, lp in enumerate(plan.levels):
                live = jnp.where(full, valid_parts[li], mask_parts[li])

                def skip(lp=lp):
                    return (jnp.zeros(lp.n_windows, bool),
                            jnp.zeros(n_stages, jnp.int32))

                surv, tiles = jax.lax.cond(
                    run[li], lambda li=li, live=live: level_head(
                        cascade, frame, li, live), skip)
                surv_parts.append(surv)
                head_tiles = head_tiles + tiles
                dense_tiles = dense_tiles + jnp.where(run[li],
                                                      level_tiles[li], 0)
            survivors = jnp.concatenate(surv_parts)
            new_bitmap = (state.bitmap & ~(mask_flat | full)) | survivors
            pix = jnp.repeat(jnp.repeat(changed, tile, axis=0),
                             tile, axis=1)[:h, :w]
            pix = jnp.pad(pix, ((0, hp - h), (0, wp - w)))
            new_ref = jnp.where(pix | full, frame, state.ref)
            new_drift = jnp.where(changed | full, 0.0,
                                  jnp.maximum(state.drift, scores))
            # the list is a function of the bitmap: compact all n_slots
            # only when a verdict changed
            changed_bm = jnp.any(new_bitmap != state.bitmap)
            slots = jax.lax.cond(
                changed_bm,
                lambda: jnp.nonzero(new_bitmap, size=decode_cap,
                                    fill_value=n_slots)[0].astype(jnp.int32),
                lambda: state.slots)
            n_surv = new_bitmap.sum().astype(jnp.int32)
            out = StreamStepOut(mode, n_tiles, n_rec, levels_active, n_surv,
                                slots, changed_bm.astype(jnp.int32),
                                head_tiles, dense_tiles)
            new_state = StreamState(
                new_ref, new_bitmap, slots, new_drift, state.frame_idx + 1,
                jnp.where(full, state.frame_idx, state.last_full))
            return new_state, out

        return jax.jit(step, donate_argnums=(1,))
