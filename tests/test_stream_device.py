"""Device-resident stream state: the on-device tile-change kernels race
their oracle twins and the host planners; `StreamConfig.device_state`
streams are bit-identical to the host-planned path (and to per-frame
``detect``) at threshold 0 across every synthetic scenario, through the
pipelined submit/retire API, large changes, and the decode-overflow
fallback; every frame, keyframes and full refreshes included, runs in one
step program per stream, whose masked fused head (``use_pallas``, step 1)
agrees with ``detect`` and the plain reference (``bench/reference.py``);
the survivor-slot list is rebuilt exactly on the frames whose bitmap
changed and carried otherwise, through a decode overflow and a reset;
the donated state reuses its buffers; and serving sessions report
identical stream stats either way."""

import numpy as np
import pytest

import jax

from repro.core import Detector, EngineConfig, paper_shaped_cascade
from repro.kernels.ops import (tile_change_mask, changed_window_map)
from repro.kernels.ref import (tile_change_mask_ref, changed_window_map_ref)
from repro.serve import DetectorService, PodSpec, ServiceConfig
from repro.stream import (SCENARIOS, StreamConfig, StreamEngine,
                          VideoDetector, make_video, tile_change_scores,
                          dilate_tiles)
from repro.stream.video import level_windows_from_raw

CASC = paper_shaped_cascade(0, stage_sizes=[3, 4, 5, 6, 8])
KW = dict(step=2, scale_factor=1.3, min_neighbors=2)
HW = 96
HOST_CFG = StreamConfig(tile=12, threshold=0.0, keyframe_interval=4)
DEV_CFG = HOST_CFG._replace(device_state=True)


@pytest.fixture(scope="module")
def detector():
    return Detector(CASC, EngineConfig(mode="wave", **KW))


def frames_of(kind, n=10, seed=3, h=HW, w=HW):
    return [f for f, _gt in make_video(kind, n_frames=n, h=h, w=w,
                                       seed=seed)]


# ------------------------------------------------- kernels vs oracles/host
@pytest.mark.parametrize("exact", [True, False])
@pytest.mark.parametrize("halo", [0, 1])
def test_tile_change_mask_matches_ref_and_host(exact, halo):
    rng = np.random.default_rng(0)
    prev = rng.random((50, 70), np.float32)
    cur = prev.copy()
    cur[12:19, 33:41] += 0.5          # a localized change
    cur[40, 2] += 1e-3                # a single-pixel tickle
    thr = 0.0 if exact else 1e-4
    changed, scores = tile_change_mask(prev, cur, thr, tile=12, halo=halo,
                                       exact=exact)
    changed_r, scores_r = tile_change_mask_ref(prev, cur, thr, tile=12,
                                               halo=halo, exact=exact)
    assert np.array_equal(np.asarray(changed), np.asarray(changed_r))
    np.testing.assert_allclose(np.asarray(scores), np.asarray(scores_r),
                               rtol=1e-5, atol=1e-7)
    # exact mode matches the host planner's bit-for-bit change test
    if exact:
        _s, host_any = tile_change_scores(prev, cur, 12, exact=True)
        host = dilate_tiles(host_any, halo)
        assert np.array_equal(np.asarray(changed), host)


def test_changed_window_map_matches_ref():
    # windows form a (ny, nx) grid with separable inclusive tile ranges:
    # rows share ty0/ty1, columns share tx0/tx1 (the streaming layout)
    rng = np.random.default_rng(1)
    ty, tx, ny, nx = 7, 9, 6, 8
    changed = rng.random((ty, tx)) < 0.3
    ty0 = rng.integers(0, ty, ny).astype(np.int32)
    ty1 = np.minimum(ty0 + rng.integers(0, 3, ny), ty - 1).astype(np.int32)
    tx0 = rng.integers(0, tx, nx).astype(np.int32)
    tx1 = np.minimum(tx0 + rng.integers(0, 3, nx), tx - 1).astype(np.int32)
    valid = rng.random(ny * nx) < 0.9
    got = changed_window_map(changed, ty0, ty1, tx0, tx1, valid)
    want = changed_window_map_ref(changed, ty0, ty1, tx0, tx1, valid)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # brute-force oracle on top: any changed tile in the inclusive range
    brute = np.array([valid[i * nx + j] and changed[ty0[i]:ty1[i] + 1,
                                                    tx0[j]:tx1[j] + 1].any()
                      for i in range(ny) for j in range(nx)])
    assert np.array_equal(np.asarray(got), brute)


# ------------------------------------------------------- bit-identity
@pytest.mark.parametrize("kind", SCENARIOS)
def test_device_stream_bit_identical_to_host_and_detect(detector, kind):
    vh = VideoDetector(detector, HOST_CFG)
    vd = VideoDetector(detector, DEV_CFG)
    for f in frames_of(kind):
        rh, sh = vh.process(f)
        rd, sd = vd.process(f)
        assert np.array_equal(rh, rd)
        assert sh == sd                  # mode, counters, level accounting
        assert np.array_equal(rd, detector.detect(f))
    assert vd.xfer_bytes > 0             # the accounting actually ran


@pytest.mark.parametrize("kind", SCENARIOS)
def test_pipelined_submit_retire_matches_sequential(detector, kind):
    # a successor's step is dispatched before its predecessor retires,
    # across keyframes and full refreshes too: the donated state chains
    # from step to step, so the answers are the sequential ones
    frames = frames_of(kind, n=12, seed=5)
    seq = VideoDetector(detector, DEV_CFG)
    pipe = VideoDetector(detector, DEV_CFG)
    want = [seq.process(f) for f in frames]
    got, prev = [], None
    for f in frames:                     # depth-2 double-buffered loop
        tok = pipe.submit(f)
        if prev is not None:
            got.append(pipe.retire(prev))
        prev = tok
    got.append(pipe.retire(prev))
    for (rw, sw), (rg, sg) in zip(want, got):
        assert np.array_equal(rw, rg) and sw == sg


def test_pan_burst_commits_in_the_one_step_program(detector):
    # static opening then a pan burst that recomputes (nearly) every
    # window: the step has no capacity to outgrow, so every frame commits
    # the exact host result in the stream's one program
    cfg_h = HOST_CFG._replace(keyframe_interval=0, full_refresh_frac=0.95,
                              max_changed_frac=0.95)
    cfg_d = cfg_h._replace(device_state=True)
    frames = (frames_of("static_cctv", n=3, seed=7)
              + frames_of("camera_pan", n=3, seed=7))
    eng = StreamEngine(detector, cfg_d.max_changed_frac)
    vh = VideoDetector(detector, cfg_h)
    vd = VideoDetector(detector, cfg_d, engine=eng)
    modes = []
    for f in frames:
        rh, sh = vh.process(f)
        rd, sd = vd.process(f)
        modes.append(sd.mode)
        assert np.array_equal(rh, rd) and sh == sd
        assert eng.program_builds == 1
    assert modes[0] == "full" and modes[1] != "full"


def test_decode_overflow_falls_back_to_full(detector):
    # decode_cap smaller than the survivor count: rects stay identical,
    # the frame is just accounted as a full refresh
    vh = VideoDetector(detector, HOST_CFG)
    vd = VideoDetector(detector, DEV_CFG, decode_cap=4)
    modes = []
    for f in frames_of("moving_face", n=8, seed=9):
        rh, _sh = vh.process(f)
        rd, sd = vd.process(f)
        modes.append(sd.mode)
        assert np.array_equal(rh, rd)
    assert set(modes) == {"full"}


# ------------------------------------------------- carried survivor slots
def face_scene(seed: int = 1, h: int = HW, w: int = HW):
    """A seeded background and the same background with a 24 px face."""
    from bench.scenes import make_background, make_face

    rng = np.random.default_rng(seed)
    bg = np.rint(make_background(rng, h, w)).astype(np.float32)
    with_face = bg.copy()
    with_face[36:60, 36:60] = np.rint(make_face(rng, 24))
    return bg, with_face


def stream_tokens(vd, frames):
    """Retire ``frames`` one by one; per frame the token and the result."""
    out = []
    for f in frames:
        tok = vd.submit(f)
        vd.poll(tok)
        out.append((tok,) + vd.commit_token(tok))
    return out


def test_slot_list_is_rebuilt_only_when_the_bitmap_changes(detector):
    # a parked camera: a face enters at frame k and leaves at frame m; the
    # keyframes at 4 and 8 see an unchanged scene, and the last frame
    # changes one corner of it by one grey level
    bg, with_face = face_scene()
    tickled = bg.copy()
    tickled[:2, -2:] += 1.0
    k, m = 5, 7
    frames = [bg] * k + [with_face] * (m - k) + [bg] * 2 + [tickled]
    eng = StreamEngine(detector)
    vd = VideoDetector(detector, DEV_CFG._replace(full_refresh_frac=0.95),
                       engine=eng)
    runs = stream_tokens(vd, frames)
    want, prev = [], np.zeros(0)
    for f in frames:
        cur = vd._slots_of(level_windows_from_raw(detector.detect_raw(f)))
        want.append((cur, int(not np.array_equal(cur, prev))))
        prev = cur
    built = [tok.flags[5] for tok, _r, _s in runs]
    assert [s.mode for _t, _r, s in runs] == [
        "full", "cached", "cached", "cached", "full", "incremental",
        "cached", "incremental", "full", "incremental"]
    assert built == [b for _c, b in want]
    assert built[0] == built[k] == built[m] == 1
    assert built[1:k] == [0] * (k - 1)            # cached, then a keyframe
    assert built[k + 1] == built[m + 1] == built[-1] == 0
    assert vd.slot_builds == 3
    for (tok, rects, _s), f, (cur, _b) in zip(runs, frames, want):
        slots = np.asarray(tok.out.slots)
        assert np.array_equal(slots[:len(cur)], cur)
        assert (slots[len(cur):] == vd._splan.n_slots).all()
        assert np.array_equal(rects, detector.detect(f))
    assert eng.program_builds == 1


def test_carried_slot_list_after_overflow_and_reset(detector):
    # more survivors than the slot list holds: the carried (truncated)
    # list stays the bitmap's, and every frame, the unchanged ones too,
    # takes its rects from a host detect; after reset the stream starts
    # from the all-fill list of the all-false bitmap
    bg, _face = face_scene()
    cap = 4
    eng = StreamEngine(detector)
    vd = VideoDetector(detector, DEV_CFG._replace(keyframe_interval=0),
                       engine=eng, decode_cap=cap)
    runs = stream_tokens(vd, [bg] * 3)
    want = vd._slots_of(level_windows_from_raw(detector.detect_raw(bg)))
    assert len(want) > cap
    assert [tok.flags[5] for tok, _r, _s in runs] == [1, 0, 0]
    for tok, rects, stats in runs:
        assert stats.mode == "full"
        assert tok.flags[4] == len(want)
        assert np.array_equal(np.asarray(tok.out.slots), want[:cap])
        assert np.array_equal(rects, detector.detect(bg))
    fill = np.full(cap, vd._splan.n_slots, np.int32)
    assert np.array_equal(np.asarray(eng.init_state(vd._splan, 0).slots),
                          fill)
    vd.reset()
    blank = np.full_like(bg, 128.0)
    (tok, rects, stats), = stream_tokens(vd, [blank])
    assert stats.mode == "full" and tok.flags[4:6] == (0, 0)
    assert np.array_equal(np.asarray(tok.out.slots), fill)
    assert np.array_equal(rects, detector.detect(blank))
    assert vd.slot_builds == 1 and eng.program_builds == 1


# ------------------------------------------------------------- residency
def test_donated_state_reuses_buffers_and_programs(detector):
    # a stream that settles into steady incremental frames: the donated
    # state must recycle its buffers in place with no new program builds
    eng = StreamEngine(detector, DEV_CFG.max_changed_frac)
    vd = VideoDetector(detector, DEV_CFG._replace(keyframe_interval=0),
                       engine=eng)
    frames = frames_of("static_cctv", n=12, seed=11)
    ptrs, builds, modes = [], [], []
    for f in frames:
        _r, s = vd.process(f)
        modes.append(s.mode)
        if vd._dev_state is not None:
            ptrs.append(vd._dev_state.ref.unsafe_buffer_pointer())
        builds.append(eng.program_builds)
    assert modes[0] == "full" and set(modes[1:]) == {"incremental"}
    # one step program, built for the opening frame (a keyframe, run on
    # the device like every other frame) and reused for every frame
    assert builds == [1] * len(frames)
    # donation: the reference-frame buffer is recycled in place
    assert len(set(ptrs[2:])) == 1
    # steady state fetches scalars + slots, never the ref/bitmap arrays
    assert vd._ref is None and vd._bitmap is None


def test_device_stream_api_guards(detector):
    vd = VideoDetector(detector, DEV_CFG)
    frame = frames_of("static_cctv", n=1)[0]
    vd.process(frame)
    with pytest.raises(RuntimeError, match="device-resident"):
        vd.plan_frame(frame)
    with pytest.raises(ValueError, match="device_state"):
        vd.reconfigure(DEV_CFG._replace(device_state=False))
    rects, _ = vd.process(frame)
    with pytest.raises(ValueError):      # cached returns are read-only
        rects[...] = 0
    vh = VideoDetector(detector, HOST_CFG)
    with pytest.raises(RuntimeError, match="device_state"):
        vh.submit(frame)
    vd.reset()                           # next frame re-opens cleanly
    r2, s2 = vd.process(frame)
    assert s2.mode == "full"
    assert np.array_equal(r2, detector.detect(frame))


# ------------------------------------------------- masked fused head
def parked_camera(n: int, h: int = 96, w: int = 128, seed: int = 13):
    """A seeded parked camera: a still uint8 scene (with planted faces)
    in which a dark 20 px square moves 12 px every other frame, then one
    frame whose whole scene brightens (a full refresh by changed tiles)."""
    from bench.scenes import render_scene

    scene = render_scene(np.random.default_rng(seed), h, w, 2)
    frames = []
    for t in range(n - 1):
        f = scene.copy()
        x = 8 + 12 * (t // 2)
        f[h - 24:h - 4, x:x + 20] = 30
        frames.append(f)
    frames.append(np.clip(frames[-1].astype(np.int32) + 9, 0, 255
                          ).astype(np.uint8))
    return frames


def test_parked_camera_masked_head_matches_detect_and_reference(
        monkeypatch):
    """Still frames, a moving object, a keyframe and a forced full
    refresh through the masked fused head: the survivors equal the plain
    reference's and the rects ``detect``'s on every frame, and the whole
    sequence runs one step program, which never reaches the packed tail."""
    from bench import reference
    from repro.kernels import packed_tail

    def no_tail(*a, **k):
        raise AssertionError("the device step must not use the packed tail")

    monkeypatch.setattr(packed_tail, "stage_sums", no_tail)
    det = Detector(CASC, EngineConfig(step=1, scale_factor=1.3,
                                      min_neighbors=2, use_pallas=True,
                                      pad_multiple=32))
    cfg = StreamConfig(tile=16, threshold=0.0, keyframe_interval=5,
                       full_refresh_frac=0.8, device_state=True)
    eng = StreamEngine(det)
    vd = VideoDetector(det, cfg, engine=eng)
    arrays = {k: np.asarray(getattr(CASC, k)) for k in (
        "rect_xywh", "rect_w", "wc_threshold", "left_val", "right_val",
        "stage_offsets", "stage_threshold")}
    rcfg = {"pad_multiple": 32, "scale_factor": 1.3}
    modes, found = [], 0
    for f in parked_camera(9):
        tok = vd.submit(f.astype(np.float32))
        vd.poll(tok)
        n_surv = tok.flags[4]
        slots = np.asarray(tok.out.slots)[:n_surv]
        rects, stats = vd.commit_token(tok)
        modes.append(stats.mode)
        geo = vd._geo
        got = np.stack([geo.lvl_of_slot[slots], geo.y_of_slot[slots],
                        geo.x_of_slot[slots]], axis=1)
        want = reference.evaluate(f, arrays, rcfg).survivors
        assert np.array_equal(got, want)
        found += len(want)
        assert np.array_equal(rects, det.detect(f.astype(np.float32)))
        assert eng.program_builds == 1
    # the object moves on even frames; frame 5 is the keyframe, and every
    # tile of the last frame changed
    assert modes == ["full", "cached", "incremental", "cached",
                     "incremental", "full", "incremental", "cached", "full"]
    assert found > 0


# --------------------------------------------------------------- serving
def test_service_sessions_identical_stats_either_way(detector):
    videos = [frames_of(k, n=6, seed=s)
              for s, k in enumerate(("static_cctv", "moving_face",
                                     "camera_pan"))]
    outs, stream_stats = [], []
    for dev in (False, True):
        svc = DetectorService(detector, ServiceConfig(
            pods=(PodSpec("big", 1.0), PodSpec("little", 0.4)),
            stream_config=HOST_CFG._replace(device_state=dev)))
        sessions = [svc.open_stream() for _ in videos]
        reqs = []
        for t in range(6):
            for sess, vid in zip(sessions, videos):
                reqs.append(sess.submit_frame(vid[t]))
        svc.flush()
        outs.append([(r.result(), r.stats) for r in reqs])
        stream_stats.append(svc.stats().stream.as_dict())
    for (rh, sh), (rd, sd) in zip(*outs):
        assert np.array_equal(rh, rd)
        assert sh == sd
    assert stream_stats[0] == stream_stats[1]


def test_jax_default_backend_is_importable():
    # the device path assumes a working jax backend; make the assumption
    # explicit so failures here are legible
    assert jax.default_backend() in ("cpu", "gpu", "tpu")
