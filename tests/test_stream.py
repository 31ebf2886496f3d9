"""Streaming golden-equivalence suite: temporal tile-reuse must never
change what the detector reports.

- static video => identical boxes to per-frame ``detect`` with >90% of
  tiles skipped after the first frame;
- threshold-0 mode => bit-identical boxes on arbitrary video (moving
  faces, pans), whatever mix of cached/incremental/full frames it takes,
  with keyframes or without;
- keyframe refresh bounds staleness when a positive threshold suppresses
  recomputation;
- the tile change test and the tile->window mapping (the device kernels
  and their ``kernels/ref.py`` twins) are exact and conservative: a tiny
  change is never absorbed, and no window touching a changed pixel is
  missed;
- the step's level accounting: cached frames and dead levels run no head,
  and a mostly-idle stream evaluates few levels.
"""

import numpy as np
import pytest

from repro import plan as planlib
from repro.core import Detector, EngineConfig, paper_shaped_cascade
from repro.core.cascade import WINDOW
from repro.core.pyramid import downscale_indices
from repro.kernels.ops import changed_window_map, tile_change_mask
from repro.kernels.ref import changed_window_map_ref, tile_change_mask_ref
from repro.stream import (SCENARIOS, StreamConfig, StreamEngine,
                          VideoDetector, make_video)

CASC = paper_shaped_cascade(0, stage_sizes=[3, 4, 5, 6, 8])
KW = dict(step=2, scale_factor=1.3, min_neighbors=2)
HW = 96


@pytest.fixture(scope="module")
def det():
    return Detector(CASC, EngineConfig(mode="wave", **KW))


@pytest.fixture(scope="module")
def engine(det):
    # one shared engine so every test reuses the same jitted programs
    return StreamEngine(det)


def _stream(det, engine, **cfg):
    return VideoDetector(det, StreamConfig(**cfg), engine=engine)


def _tile_masks(prev, cur, tile, halo=0, exact=True, threshold=0.0):
    """(changed, scores) of the device kernel and of its ref twin."""
    got = tile_change_mask(prev, cur, threshold, tile=tile, halo=halo,
                           exact=exact)
    want = tile_change_mask_ref(prev, cur, threshold, tile=tile, halo=halo,
                                exact=exact)
    return [tuple(np.asarray(a) for a in r) for r in (got, want)]


# ---------------------------------------------------------------- identity
def test_static_video_identical_with_skips(det, engine):
    rng = np.random.default_rng(5)
    from repro.core.training.data import render_scene
    frame = render_scene(rng, HW, HW, n_faces=1)[0]
    base = det.detect(frame)
    vd = _stream(det, engine, tile=16, threshold=0.0, keyframe_interval=0)
    for t in range(5):
        rects, st = vd.process(frame)
        assert np.array_equal(rects, base)
        if t == 0:
            assert st.mode == "full"
        else:
            assert st.mode == "cached"
            assert st.tile_skip_frac == 1.0
            assert st.tile_skip_frac > 0.9          # the ISSUE's bar
            assert st.windows_recomputed == 0


@pytest.mark.parametrize("keyframe_interval", [0, 4])
@pytest.mark.parametrize("kind", SCENARIOS)
def test_threshold0_bit_identical(det, engine, kind, keyframe_interval):
    video = make_video(kind, n_frames=8, h=HW, w=HW, seed=11)
    vd = _stream(det, engine, tile=12, threshold=0.0,
                 keyframe_interval=keyframe_interval)
    modes = []
    for frame, _gt in video:
        rects, st = vd.process(frame)
        assert np.array_equal(rects, det.detect(frame)), \
            f"{kind} frame {st.frame_idx} ({st.mode}) diverged"
        modes.append(st.mode)
    assert vd.xfer_bytes > 0             # the accounting actually ran
    if kind == "static_cctv":   # the small moving object stays incremental
        assert "incremental" in modes
    fulls = [i for i, m in enumerate(modes) if m == "full"]
    assert fulls[0] == 0
    if keyframe_interval:       # a keyframe at most every interval frames
        assert all(b - a <= keyframe_interval
                   for a, b in zip(fulls, fulls[1:] + [len(modes)]))


def test_incremental_path_skips_windows(det, engine):
    video = make_video("static_cctv", n_frames=4, h=HW, w=HW, seed=2)
    vd = _stream(det, engine, tile=12, threshold=0.0, keyframe_interval=0)
    for i, (frame, _gt) in enumerate(video):
        rects, st = vd.process(frame)
        if i > 0:
            assert st.mode == "incremental"
            assert 0 < st.windows_recomputed < st.windows_total
            assert st.window_skip_frac > 0.5
        assert np.array_equal(rects, det.detect(frame))


# ---------------------------------------------------------------- keyframe
def test_keyframe_bounds_staleness(det, engine):
    video_a = make_video("static_cctv", n_frames=1, h=HW, w=HW, seed=3)
    video_b = make_video("static_cctv", n_frames=1, h=HW, w=HW, seed=4)
    frame_a, frame_b = video_a[0][0], video_b[0][0]
    base_a, base_b = det.detect(frame_a), det.detect(frame_b)
    # a huge threshold suppresses all incremental recomputation: only the
    # keyframe cadence refreshes the cache
    vd = _stream(det, engine, tile=16, threshold=1e12, keyframe_interval=4)
    for _ in range(3):
        rects, st = vd.process(frame_a)
    # scene cut: frames 3.. show scene B, but stay stale until the keyframe
    rects, st = vd.process(frame_b)
    assert st.mode == "cached"
    assert np.array_equal(rects, base_a)            # stale by design
    rects, st = vd.process(frame_b)                 # frame 4 == keyframe
    assert st.mode == "full"
    assert np.array_equal(rects, base_b)            # staleness bounded


def test_positive_threshold_keyframes_match_detect(det, engine):
    """Slow lighting drift under a positive threshold: most frames reuse
    the cache (stale by at most the threshold), and every full frame, the
    keyframes included, is exactly ``detect``'s."""
    video = make_video("lighting_drift", n_frames=9, h=HW, w=HW, seed=5)
    vd = _stream(det, engine, tile=16, threshold=4.0, keyframe_interval=4)
    modes = []
    for frame, _gt in video:
        rects, st = vd.process(frame)
        modes.append(st.mode)
        if st.mode == "full":
            assert np.array_equal(rects, det.detect(frame))
    assert modes[0] == modes[4] == modes[8] == "full"
    assert "cached" in modes


def test_keyframe_disabled_never_refreshes(det, engine):
    frame = make_video("static_cctv", n_frames=1, h=HW, w=HW, seed=3)[0][0]
    vd = _stream(det, engine, tile=16, threshold=1e12, keyframe_interval=0)
    vd.process(frame)
    for _ in range(6):
        _rects, st = vd.process(frame)
        assert st.mode == "cached"


# ----------------------------------------------------------- tile mapping
def test_tile_change_scores_localized():
    prev = np.full((64, 64), 100.0, np.float32)
    cur = prev.copy()
    cur[40, 9] += 3.0
    for changed, scores in _tile_masks(prev, cur, 16):
        assert changed.sum() == 1 and changed[2, 0]
        assert scores[2, 0] > 0
        assert (scores[~changed] < 1e-6).all()


def test_tile_change_any_immune_to_absorption():
    """A tiny change must be flagged even next to a huge one (float sums
    would absorb it; the exact any-reduction must not)."""
    prev = np.zeros((64, 64), np.float32)
    cur = prev.copy()
    cur[:16, :16] = 255.0                 # huge change, tile (0,0)
    cur[40, 40] = 1e-4                    # tiny change, tile (2,2)
    for changed, _scores in _tile_masks(prev, cur, 16):
        assert changed[0, 0] and changed[2, 2]
        assert changed.sum() == 2


def test_dilate_tiles():
    prev = np.zeros((80, 80), np.float32)
    cur = prev.copy()
    cur[40, 40] = 1.0                     # tile (2, 2) of a 5 x 5 grid
    for d, _s in _tile_masks(prev, cur, 16, halo=1):
        assert d.sum() == 5 and d[1, 2] and d[3, 2] and d[2, 1] and d[2, 3]
    for d, _s in _tile_masks(prev, cur, 16, halo=0):
        assert d.sum() == 1 and d[2, 2]


def test_changed_window_mask_is_conservative(det):
    """Property: every window whose receptive field touches a changed pixel
    must be in the mask (brute force over the nearest-neighbour map), for
    the device kernel and its ref twin over the compiled tile ranges."""
    rng = np.random.default_rng(9)
    tile = 16
    splan = planlib.compile_stream_plan(det.config, det.n_stages, 64, 64,
                                        64, 64, tile, 0)
    levels = det.batch_plan(64, 64).levels
    for _ in range(3):
        changed = rng.random((4, 4)) < 0.3
        if not changed.any():
            continue
        pix = np.repeat(np.repeat(changed, tile, 0), tile, 1)
        for lp, rng_l in zip(levels, splan.level_tile_ranges):
            valid = splan.limit_mask[lp.slot_offset:
                                     lp.slot_offset + lp.n_windows]
            ys_map = downscale_indices(64, lp.height)
            xs_map = downscale_indices(64, lp.width)
            for fn in (changed_window_map, changed_window_map_ref):
                mask = np.asarray(fn(changed, *rng_l, valid)
                                  ).reshape(lp.ny, lp.nx)
                for iy in range(lp.ny):
                    for ix in range(lp.nx):
                        y, x = iy * det.config.step, ix * det.config.step
                        rows = ys_map[y:y + WINDOW]
                        cols = xs_map[x:x + WINDOW]
                        if pix[np.ix_(rows, cols)].any():
                            assert mask[iy, ix], (lp, iy, ix)


# ------------------------------------------------------------- lifecycle
def test_frame_shape_change_raises(det, engine):
    vd = _stream(det, engine, tile=16)
    vd.process(np.zeros((HW, HW), np.float32))
    with pytest.raises(ValueError, match="shape changed"):
        vd.process(np.zeros((HW, HW + 2), np.float32))
    with pytest.raises(ValueError, match="grayscale"):
        VideoDetector(det).process(np.zeros((4, HW, HW), np.float32))


def test_sub_window_stream_is_empty(det, engine):
    vd = _stream(det, engine, tile=8)
    for _ in range(2):
        rects, _st = vd.process(np.zeros((10, 10), np.float32))
        assert rects.shape == (0, 4)


def test_repeated_frame_after_keyframe_is_cached(det, engine):
    """A bit-identical frame after a keyframe: the step reads cached,
    recomputes nothing, runs no head, keeps its slot list, and compiles
    nothing new."""
    frames = [f for f, _gt in make_video("static_cctv", n_frames=3, h=HW,
                                         w=HW, seed=8)]
    vd = _stream(det, engine, tile=12, threshold=0.0, keyframe_interval=2)
    for f in frames:
        vd.process(f)                    # frame 2 is the keyframe
    builds = (engine.program_builds, det.program_builds)
    tok = vd.submit(frames[-1])
    rects, st = vd.retire(tok)
    assert st.mode == "cached"
    assert st.windows_recomputed == 0 and st.levels_active == 0
    assert tok.flags[2] == 0 and tok.flags[3] == 0
    assert tok.flags[5] == 0             # slot list carried, not rebuilt
    assert (engine.program_builds, det.program_builds) == builds
    assert np.array_equal(rects, det.detect(frames[-1]))


def test_large_change_commits_full(det, engine):
    """More than ``full_refresh_frac`` of the tiles changed: the step
    commits a full refresh, identical to ``detect``."""
    frame0 = make_video("static_cctv", n_frames=1, h=HW, w=HW,
                        seed=12)[0][0]
    bright = np.clip(frame0 + 9.0, 0, 255).astype(np.float32)
    vd = _stream(det, engine, tile=12, threshold=0.0, keyframe_interval=0,
                 full_refresh_frac=0.5)
    vd.process(frame0)
    rects, st = vd.process(bright)
    assert st.tiles_changed == st.tiles_total
    assert st.mode == "full" and st.windows_recomputed == st.windows_total
    assert np.array_equal(rects, det.detect(bright))
    touched = bright.copy()
    touched[:4, :4] += 1.0               # and back to incremental work
    rects, st = vd.process(touched)
    assert st.mode == "incremental"
    assert np.array_equal(rects, det.detect(touched))


def test_reset_mid_stream_reopens_with_keyframe(det, engine):
    frames = [f for f, _gt in make_video("moving_face", n_frames=6, h=HW,
                                         w=HW, seed=6)]
    vd = _stream(det, engine, tile=12, threshold=0.0, keyframe_interval=0)
    for f in frames[:3]:
        vd.process(f)
    vd.reset()
    modes = []
    for f in frames[3:]:
        rects, st = vd.process(f)
        modes.append(st.mode)
        assert np.array_equal(rects, det.detect(f))
    assert modes[0] == "full"
    assert vd.frame_idx == len(frames)   # the frame counter runs on


def test_device_state_false_is_refused(det):
    with pytest.raises(ValueError, match="device-resident"):
        VideoDetector(det, StreamConfig(device_state=False))
    assert StreamConfig().device_state


# ------------------------------------------------------- level accounting
def test_incremental_plan_reports_active_levels(det, engine):
    """``levels_active`` counts the levels whose changed-window map (the
    ref twins over the compiled tile ranges) has a live window."""
    video = make_video("static_cctv", n_frames=2, h=HW, w=HW, seed=2)
    vd = _stream(det, engine, tile=12, threshold=0.0, keyframe_interval=0,
                 halo=1)
    vd.process(video[0][0])
    _rects, st = vd.process(video[1][0])
    assert st.mode == "incremental"
    splan = vd._splan
    changed, _s = tile_change_mask_ref(video[0][0], video[1][0], 0.0,
                                       tile=12, halo=1)
    want = 0
    for lp, rng_l in zip(det.batch_plan(HW, HW).levels,
                         splan.level_tile_ranges):
        valid = splan.limit_mask[lp.slot_offset:
                                 lp.slot_offset + lp.n_windows]
        want += bool(np.asarray(changed_window_map_ref(changed, *rng_l,
                                                       valid)).any())
    assert st.levels_active == want >= 1


def test_fully_cached_levels_build_no_sat(det):
    """Padded bucket: a 48-row frame in a 64-row bucket has zero live
    windows at the coarsest pyramid level (its windows would sample padded
    pixels), so no incremental frame ever counts that level active — and
    results stay bit-identical to per-frame detect."""
    pad_det = Detector(CASC, EngineConfig(mode="wave", pad_multiple=64, **KW))
    engine = StreamEngine(pad_det)
    video = make_video("static_cctv", n_frames=3, h=48, w=64, seed=6)
    vd = VideoDetector(pad_det, StreamConfig(tile=12, threshold=0.0,
                                             keyframe_interval=0,
                                             full_refresh_frac=1.1),
                       engine=engine)
    levels = pad_det.batch_plan(64, 64).levels
    dead = [lp for lp in levels
            if planlib.window_limits(48, 64, lp.height, lp.width,
                                     64, 64)[0] < 0]
    assert dead, "fixture must have at least one dead (fully-cached) level"
    n_incr = 0
    for frame, _gt in video:
        rects, st = vd.process(frame)
        assert np.array_equal(rects, pad_det.detect(frame))
        if st.mode == "incremental":
            n_incr += 1
            assert 1 <= st.levels_active <= len(levels) - len(dead)
            assert st.level_skip_frac > 0
    assert n_incr >= 1


def test_cached_frame_builds_no_sat(det, engine):
    """A bit-identical frame evaluates nothing: no level active, no slot
    list rebuilt, no program built."""
    frame = make_video("static_cctv", n_frames=1, h=HW, w=HW, seed=3)[0][0]
    vd = _stream(det, engine, tile=16, threshold=0.0, keyframe_interval=0)
    vd.process(frame)
    before = (vd.slot_builds, engine.program_builds)
    _rects, st = vd.process(frame)
    assert st.mode == "cached"
    assert st.levels_active == 0 and st.level_skip_frac == 1.0
    assert (vd.slot_builds, engine.program_builds) == before


def test_intermittent_stream_level_sat_frac(det, engine):
    """Mostly-idle stream: averaged over frames, fewer than half the
    pyramid levels run the head, and output stays bit-identical."""
    video = make_video("intermittent_cctv", n_frames=8, h=HW, w=HW, seed=4)
    vd = _stream(det, engine, tile=12, threshold=0.0, keyframe_interval=0)
    fracs = []
    for i, (frame, _gt) in enumerate(video):
        rects, st = vd.process(frame)
        assert np.array_equal(rects, det.detect(frame))
        if i > 0:
            fracs.append(st.levels_active / max(st.levels_total, 1))
            assert st.mode in ("cached", "incremental")
    assert np.mean(fracs) < 0.5, fracs
