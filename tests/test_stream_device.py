"""Device-resident stream state: the on-device tile-change kernels race
their oracle twins and a brute-force numpy test; streams stay
bit-identical to per-frame ``detect`` at threshold 0 through the
pipelined submit/retire API, large changes, and the decode-overflow
fallback; every frame, keyframes and full refreshes included, runs in one
step program per stream, whose masked fused head (``use_pallas``, step 1)
agrees with ``detect`` and the plain reference (``bench/reference.py``);
the survivor-slot list is rebuilt exactly on the frames whose bitmap
changed and carried otherwise, through a decode overflow and a reset;
the donated state reuses its buffers and moves only the frame, the
verdict and the slot list; and serving sessions report the stats and
rects a single-stream ``VideoDetector`` does, across shape buckets and
decode overflows."""

import numpy as np
import pytest

import jax

from repro.core import Detector, EngineConfig, paper_shaped_cascade
from repro.kernels.ops import (tile_change_mask, changed_window_map)
from repro.kernels.ref import (tile_change_mask_ref, changed_window_map_ref)
from repro.serve import DetectorService, PodSpec, ServiceConfig
from repro.stream import (SCENARIOS, StreamConfig, StreamEngine,
                          VideoDetector, level_windows_from_raw, make_video)

CASC = paper_shaped_cascade(0, stage_sizes=[3, 4, 5, 6, 8])
KW = dict(step=2, scale_factor=1.3, min_neighbors=2)
HW = 96
DEV_CFG = StreamConfig(tile=12, threshold=0.0, keyframe_interval=4)


@pytest.fixture(scope="module")
def detector():
    return Detector(CASC, EngineConfig(mode="wave", **KW))


def frames_of(kind, n=10, seed=3, h=HW, w=HW):
    return [f for f, _gt in make_video(kind, n_frames=n, h=h, w=w,
                                       seed=seed)]


def brute_changed(prev, cur, tile, halo):
    """Any pixel differs per tile, then ``halo`` 4-neighbour rings."""
    ty, tx = -(-cur.shape[0] // tile), -(-cur.shape[1] // tile)
    m = np.array([[(prev[i * tile:(i + 1) * tile, j * tile:(j + 1) * tile]
                    != cur[i * tile:(i + 1) * tile,
                           j * tile:(j + 1) * tile]).any()
                   for j in range(tx)] for i in range(ty)])
    for _ in range(halo):
        g = m.copy()
        g[1:] |= m[:-1]
        g[:-1] |= m[1:]
        g[:, 1:] |= m[:, :-1]
        g[:, :-1] |= m[:, 1:]
        m = g
    return m


# ------------------------------------------------ kernels vs oracles
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
    # exact mode is the brute-force any-pixel-differs test, dilated
    if exact:
        assert np.array_equal(np.asarray(changed),
                              brute_changed(prev, cur, 12, halo))


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
    # the exact result in the stream's one program
    cfg = DEV_CFG._replace(keyframe_interval=0, full_refresh_frac=0.95)
    frames = (frames_of("static_cctv", n=3, seed=7)
              + frames_of("camera_pan", n=3, seed=7))
    eng = StreamEngine(detector)
    vd = VideoDetector(detector, cfg, engine=eng)
    modes = []
    for f in frames:
        rd, sd = vd.process(f)
        modes.append(sd.mode)
        assert np.array_equal(rd, detector.detect(f))
        assert eng.program_builds == 1
    assert modes[0] == "full" and modes[1] != "full"


def test_decode_overflow_falls_back_to_full(detector):
    # decode_cap smaller than the survivor count: rects stay identical,
    # the frame is just accounted as a full refresh
    vd = VideoDetector(detector, DEV_CFG, decode_cap=4)
    modes = []
    for f in frames_of("moving_face", n=8, seed=9):
        rd, sd = vd.process(f)
        modes.append(sd.mode)
        assert np.array_equal(rd, detector.detect(f))
        assert sd.windows_recomputed == sd.windows_total
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
    eng = StreamEngine(detector)
    vd = VideoDetector(detector, DEV_CFG._replace(keyframe_interval=0),
                       engine=eng)
    frames = frames_of("static_cctv", n=12, seed=11)
    ptrs, builds, modes, xfer = [], [], [], []
    for f in frames:
        before = vd.xfer_bytes
        _r, s = vd.process(f)
        xfer.append(vd.xfer_bytes - before)
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
    # steady state moves the frame up, the verdict and the slot list
    # down, never the reference or the bitmap
    splan = vd._splan
    step_bytes = (splan.hp * splan.wp * 4 + (7 + detector.n_stages) * 4
                  + splan.decode_cap * 4)
    assert xfer[1:] == [step_bytes] * (len(frames) - 1)


def test_device_stream_api_guards(detector):
    with pytest.raises(ValueError, match="device_state"):
        VideoDetector(detector, DEV_CFG._replace(device_state=False))
    vd = VideoDetector(detector, DEV_CFG)
    frame = frames_of("static_cctv", n=1)[0]
    vd.process(frame)
    with pytest.raises(ValueError, match="device_state"):
        vd.reconfigure(DEV_CFG._replace(device_state=False))
    assert vd.config == DEV_CFG          # the refused config never lands
    rects, _ = vd.process(frame)
    with pytest.raises(ValueError):      # cached returns are read-only
        rects[...] = 0
    tok_a, tok_b = vd.submit(frame), vd.submit(frame)
    with pytest.raises(RuntimeError, match="submit order"):
        vd.retire(tok_b)
    vd.retire(tok_a)
    vd.retire(tok_b)
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
def serve_round_robin(svc, sessions, videos):
    """Submit frame t of every session, for every t, then flush once."""
    reqs = []
    for t in range(len(videos[0])):
        for sess, vid in zip(sessions, videos):
            reqs.append(sess.submit_frame(vid[t]))
    svc.flush()
    return [(r.result(), r.stats) for r in reqs]


def test_service_sessions_identical_stats_either_way(detector):
    # sessions served round by round report the rects and stats of the
    # same streams run alone, and the rects of per-frame detect
    videos = [frames_of(k, n=6, seed=s)
              for s, k in enumerate(("static_cctv", "moving_face",
                                     "camera_pan"))]
    svc = DetectorService(detector, ServiceConfig(
        pods=(PodSpec("big", 1.0), PodSpec("little", 0.4)),
        stream_config=DEV_CFG))
    got = serve_round_robin(svc, [svc.open_stream() for _ in videos],
                            videos)
    alone = [VideoDetector(detector, DEV_CFG) for _ in videos]
    want = [alone[i].process(videos[i][t])
            for t in range(6) for i in range(len(videos))]
    flat = [videos[i][t] for t in range(6) for i in range(len(videos))]
    modes = {"full": 0, "incremental": 0, "cached": 0}
    for (rg, sg), (rw, sw), f in zip(got, want, flat):
        assert np.array_equal(rg, rw) and sg == sw
        assert np.array_equal(rg, detector.detect(f))
        modes[sg.mode] += 1
    stream = svc.stats().stream
    assert stream.frames_done == len(flat)
    assert stream.frame_modes == modes


def test_service_round_two_frame_shapes(detector):
    # two plan keys in one round: each session runs its own bucket's step
    # program, and every frame matches detect
    videos = [frames_of("static_cctv", n=4, seed=1),
              frames_of("moving_face", n=4, seed=2, h=64, w=80)]
    svc = DetectorService(detector, ServiceConfig(stream_config=DEV_CFG))
    sessions = [svc.open_stream() for _ in videos]
    got = serve_round_robin(svc, sessions, videos)
    flat = [videos[i][t] for t in range(4) for i in range(2)]
    for (rects, _st), f in zip(got, flat):
        assert np.array_equal(rects, detector.detect(f))
    assert len({s.plan_key for s in sessions}) == 2
    assert svc.stream_engine.program_builds == 2


def test_service_decode_overflow_counts_full(detector):
    # a session whose survivors overflow its slot list: every frame takes
    # its rects from a host detect and counts as a full frame
    bg, with_face = face_scene()
    frames = [bg, bg, with_face, with_face]
    svc = DetectorService(detector, ServiceConfig(stream_config=DEV_CFG))
    sess = svc.open_stream()
    sess.video = VideoDetector(detector, DEV_CFG,
                               engine=svc.stream_engine, decode_cap=4)
    got = serve_round_robin(svc, [sess], [frames])
    for (rects, st), f in zip(got, frames):
        assert st.mode == "full"
        assert st.windows_recomputed == st.windows_total
        assert np.array_equal(rects, detector.detect(f))
    assert svc.stats().stream.frame_modes["full"] == len(frames)


def test_jax_default_backend_is_importable():
    # the device path assumes a working jax backend; make the assumption
    # explicit so failures here are legible
    assert jax.default_backend() in ("cpu", "gpu", "tpu")
