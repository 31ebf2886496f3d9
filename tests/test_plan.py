"""The cascade plan layer: plan caching, derivation equivalence, and the
plan→compile→execute contract.

- ``compile_plan`` / ``compile_level_plan`` are cached on their full
  identity, so repeated ``detect`` / ``detect_batch`` / stream calls on
  the same bucket must not rebuild any program (``Detector.program_builds``
  / ``StreamEngine.program_builds`` are the regression probes);
- the plan's segments, capacity ladders, and slot/SAT layout must equal
  the legacy builders' inline derivations (the formulas the engines used
  to recompute independently);
- the per-segment tail backend is the plan's decision off the
  ``tail_rungs`` crossover ladder, and executors consume it as compiled;
- the device stream step's plan (``compile_stream_plan``) is cached on its
  identity and its tile ranges bracket every window's receptive field;
- plan-built executors stay bit-identical across strategies and the
  threshold-0 streaming path (the cross-checks the equivalence suites in
  ``test_engine_batch`` / ``test_stream`` enforce corpus-wide).
"""

import numpy as np
import pytest

import repro.plan as planlib
from repro.core import Detector, EngineConfig, paper_shaped_cascade
from repro.core.cascade import WINDOW
from repro.core.training.data import render_scene
from repro.stream import StreamConfig, StreamEngine, VideoDetector, make_video

CASC = paper_shaped_cascade(0, stage_sizes=[3, 4, 5, 6, 8])
N_STAGES = CASC.n_stages
KW = dict(step=2, scale_factor=1.3, min_neighbors=2)
CFG = EngineConfig(mode="wave", **KW)


# ------------------------------------------------------------ plan caching
def test_compile_plan_is_cached():
    a = planlib.compile_plan(CFG, N_STAGES, 64, 64, batch=2)
    b = planlib.compile_plan(CFG, N_STAGES, 64, 64, batch=2)
    assert a is b                      # same object, not just equal
    c = planlib.compile_plan(CFG, N_STAGES, 64, 64, batch=3)
    assert c is not a and c.key != a.key
    lp = planlib.compile_level_plan(CFG, N_STAGES, 64, 64)
    assert planlib.compile_level_plan(CFG, N_STAGES, 64, 64) is lp


def test_detect_paths_never_rebuild_programs():
    """Repeated detect / detect_batch (both strategies) on the same bucket:
    zero program rebuilds after the first call."""
    det = Detector(CASC, CFG)
    rng = np.random.default_rng(0)
    imgs = [render_scene(rng, 64, 64, n_faces=1)[0] for _ in range(3)]
    det.detect(imgs[0])
    det.detect_batch(imgs, strategy="packed")
    det.detect_batch(imgs, strategy="vmap")
    builds = det.program_builds
    assert builds > 0
    for _ in range(2):
        det.detect(imgs[1])
        det.detect_batch(imgs, strategy="packed")
        det.detect_batch(imgs, strategy="vmap")
    assert det.program_builds == builds


def test_stream_never_rebuilds_programs():
    det = Detector(CASC, CFG)
    engine = StreamEngine(det)
    video = make_video("moving_face", n_frames=4, h=64, w=64, seed=2)
    vd = VideoDetector(det, StreamConfig(tile=16, threshold=0.0,
                                         keyframe_interval=0), engine=engine)
    for f, _gt in video:
        vd.process(f)
    builds = (det.program_builds, engine.program_builds)
    assert engine.program_builds == 1
    vd2 = VideoDetector(det, StreamConfig(tile=16, threshold=0.0,
                                          keyframe_interval=0),
                        engine=engine)
    got = [vd2.process(f)[0] for f, _gt in video]
    assert (det.program_builds, engine.program_builds) == builds
    for rects, (f, _gt) in zip(got, video):
        assert np.array_equal(rects, det.detect(f))


def test_stream_plan_keys_and_cache():
    """The stream plan is cached on its identity, and its key tells apart
    every field that shapes the step program."""
    args = (CFG, N_STAGES, 96, 96, 90, 80, 16, 1)
    base = planlib.compile_stream_plan(*args)
    assert planlib.compile_stream_plan(*args) is base
    variants = [planlib.compile_stream_plan(CFG, N_STAGES, 96, 96, 96, 96,
                                            16, 1),
                planlib.compile_stream_plan(CFG, N_STAGES, 96, 96, 90, 80,
                                            12, 1),
                planlib.compile_stream_plan(CFG, N_STAGES, 96, 96, 90, 80,
                                            16, 0),
                planlib.compile_stream_plan(CFG, N_STAGES, 96, 96, 90, 80,
                                            16, 1, decode_cap=64)]
    keys = {base.key} | {v.key for v in variants}
    assert len(keys) == 5
    assert variants[-1].decode_cap == 64
    assert base.decode_cap == min(planlib.STREAM_DECODE_CAP, base.n_slots)
    assert (base.ty, base.tx) == (-(-90 // 16), -(-80 // 16))


@pytest.mark.parametrize("halo", [0, 1])
def test_stream_plan_tile_ranges_match_receptive_fields(halo):
    """Each window's (ty0, ty1, tx0, tx1) bracket is the first and last
    tile its receptive field samples (brute force over the
    nearest-neighbour map), clipped to the true frame's tile grid; the
    limit mask keeps exactly the windows that sample no padding."""
    from repro.core.pyramid import downscale_indices
    hp = wp = 96
    h, w, tile = 90, 80, 16
    sp = planlib.compile_stream_plan(CFG, N_STAGES, hp, wp, h, w, tile,
                                     halo)
    plan = planlib.compile_plan(CFG, N_STAGES, hp, wp)
    assert len(sp.level_tile_ranges) == len(plan.levels)
    n_live = 0
    for lp, (ty0, ty1, tx0, tx1) in zip(plan.levels, sp.level_tile_ranges):
        ys_map = downscale_indices(hp, lp.height)
        xs_map = downscale_indices(wp, lp.width)
        for iy in range(lp.ny):
            rows = ys_map[iy * CFG.step:iy * CFG.step + WINDOW]
            assert ty0[iy] == min(rows.min() // tile, sp.ty - 1)
            assert ty1[iy] == min(rows.max() // tile, sp.ty - 1)
        for ix in range(lp.nx):
            cols = xs_map[ix * CFG.step:ix * CFG.step + WINDOW]
            assert tx0[ix] == min(cols.min() // tile, sp.tx - 1)
            assert tx1[ix] == min(cols.max() // tile, sp.tx - 1)
        rows_ok = np.array([ys_map[iy * CFG.step + WINDOW - 1] < h
                            for iy in range(lp.ny)])
        cols_ok = np.array([xs_map[ix * CFG.step + WINDOW - 1] < w
                            for ix in range(lp.nx)])
        want = (rows_ok[:, None] & cols_ok[None, :]).reshape(-1)
        got = sp.limit_mask[lp.slot_offset:lp.slot_offset + lp.n_windows]
        assert np.array_equal(got, want)
        n_live += int(want.sum())
    assert sp.n_live == n_live and sp.n_slots == plan.n_slots


# ----------------------------------------------------- derivation identity
def test_segments_match_legacy_formula():
    for cfg in (CFG, CFG._replace(mode="dense"),
                CFG._replace(dense_segments=(1,), compact_every=2),
                CFG._replace(dense_segments=(2, 4, 8))):
        spans = planlib.segment_spans(N_STAGES, cfg)
        # legacy inline derivation (what Detector._segments used to do)
        if cfg.mode == "dense":
            want = [(0, N_STAGES, True)]
        else:
            want, s = [], 0
            for ds in cfg.dense_segments:
                if s >= N_STAGES:
                    break
                s1 = min(s + ds, N_STAGES)
                want.append((s, s1, True))
                s = s1
            while s < N_STAGES:
                s1 = min(s + cfg.compact_every, N_STAGES)
                want.append((s, s1, False))
                s = s1
        assert list(spans) == want
        assert spans[-1][1] == N_STAGES
        assert Detector(CASC, cfg)._segments() == want


def test_capacity_ladders_match_legacy_formula():
    import math
    n_windows, batch = 1234, 4
    spans = planlib.segment_spans(N_STAGES, CFG)
    n_comp = planlib.n_compactions(spans)
    got = planlib.level_capacities(n_windows, n_comp, ())
    want = []
    for i in range(n_comp):
        f = max(0.5 ** i, 0.08)
        want.append(min(max(int(math.ceil(n_windows * min(f, 1.0))),
                            planlib.CAP_FLOOR), n_windows))
    assert list(got) == want
    cfgf = CFG._replace(batch_capacity_fracs=tuple([0.5] * n_comp))
    got_b = planlib.shared_capacities(n_windows, batch, n_comp, cfgf)
    total = n_windows * batch
    want_b, prev = [], total
    for _ in range(n_comp):
        cap = min(max(int(math.ceil(total * 0.5)), planlib.BATCH_CAP_FLOOR),
                  prev)
        want_b.append(cap)
        prev = cap
    assert list(got_b) == want_b


def test_plan_levels_match_pyramid():
    from repro.core.pyramid import pyramid_plan
    plan = planlib.compile_plan(CFG, N_STAGES, 96, 80)
    pyr = pyramid_plan(96, 80, CFG.scale_factor)
    assert len(plan.levels) == len(pyr)
    off = 0
    for lp, lv in zip(plan.levels, pyr):
        assert (lp.height, lp.width, lp.scale) == tuple(lv)
        assert lp.ny == (lv.height - WINDOW) // CFG.step + 1
        assert lp.nx == (lv.width - WINDOW) // CFG.step + 1
        assert lp.slot_offset == off
        off += lp.ny * lp.nx
    assert plan.n_slots == off == plan.n_windows_total


def test_slot_layout_matches_bruteforce():
    plan = planlib.compile_plan(CFG, N_STAGES, 96, 96)
    lo = plan.layout
    lvl, ys, xs, bases = [], [], [], [0]
    for lp in plan.levels:
        gy = np.arange(lp.ny) * CFG.step
        gx = np.arange(lp.nx) * CFG.step
        lvl.append(np.full(lp.ny * lp.nx, lp.index))
        ys.append(np.repeat(gy, lp.nx))
        xs.append(np.tile(gx, lp.ny))
        bases.append(bases[-1] + (lp.height + 1) * (lp.width + 1))
    assert np.array_equal(lo.lvl_of_slot, np.concatenate(lvl))
    assert np.array_equal(lo.y_of_slot, np.concatenate(ys))
    assert np.array_equal(lo.x_of_slot, np.concatenate(xs))
    assert np.array_equal(lo.sat_base_of_lvl, bases[:-1])
    assert np.array_equal(lo.sat_stride_of_lvl,
                          [lp.width + 1 for lp in plan.levels])


def test_slot_layout_of_a_bucket_without_windows():
    # a bucket smaller than the detection window has no level and no slot
    plan = planlib.compile_plan(CFG, N_STAGES, 16, 16)
    lo = plan.layout
    assert plan.levels == () and lo.n_slots == plan.n_slots == 0
    assert lo.lvl_of_slot.shape == lo.y_of_slot.shape == (0,)
    assert np.array_equal(lo.sat_base_of_lvl, [0])


# ------------------------------------------------------- backend decisions
LADDER = ((128, "gather"), (1024, "bulk"), (8192, "pallas"))


def test_tail_backends_compiled_into_plan():
    cfg = CFG._replace(tail_backend="auto", tail_rungs=LADDER,
                       dense_segments=(1,), compact_every=2)
    plan = planlib.compile_plan(cfg, N_STAGES, 96, 96, batch=4)
    assert plan.tail_segments      # the shape actually exercises a tail
    for seg in plan.tail_segments:
        assert seg.backend == planlib.select_backend(cfg, seg.capacity)
        assert seg.backend in ("gather", "bulk", "pallas")


def test_packed_tail_select_backend_delegates_to_plan():
    from repro.kernels import packed_tail
    cfg = EngineConfig(tail_backend="auto", tail_rungs=LADDER)
    for n in (1, 128, 129, 5000, 10**6):
        assert (packed_tail.select_backend(cfg, n)
                == planlib.select_backend(cfg, n))


# --------------------------------------------------- head-mode decisions
PALL = CFG._replace(use_pallas=True, step=1)
HEAD_LADDER = ((100, "fused"), (1000, "split"), (10 ** 6, "fused"))


def test_head_modes_compiled_into_plan():
    # forced modes win at every level (level plans and batch plans alike)
    for forced in ("fused", "split"):
        cfg = PALL._replace(head_mode=forced)
        plan = planlib.compile_plan(cfg, N_STAGES, 64, 64)
        assert set(plan.head_modes) == {forced}
        assert len(plan.head_modes) == len(plan.levels)
        lp = planlib.compile_level_plan(cfg, N_STAGES, 64, 64)
        assert lp.head_mode == forced
    # auto + empty ladder -> fused; a calibrated ladder is walked by the
    # level's window count exactly like the tail's crossover rungs
    assert planlib.select_head_mode(PALL, 10) == "fused"
    tuned = PALL._replace(head_rungs=HEAD_LADDER)
    assert planlib.select_head_mode(tuned, 50) == "fused"
    assert planlib.select_head_mode(tuned, 500) == "split"
    assert planlib.select_head_mode(tuned, 10 ** 7) == "fused"  # past end
    plan = planlib.compile_plan(tuned, N_STAGES, 96, 96)
    assert plan.head_modes == tuple(
        planlib.select_head_mode(tuned, lp.n_windows) for lp in plan.levels)
    # strided / non-Pallas configs never get the fused option
    assert planlib.select_head_mode(CFG, 10 ** 6) == "split"
    assert planlib.select_head_mode(CFG._replace(step=1), 10) == "split"
    for cfg in (CFG, CFG._replace(step=1),
                PALL._replace(use_pallas=False, head_mode="fused")):
        assert set(planlib.compile_plan(cfg, N_STAGES, 64, 64).head_modes) \
            == {"split"}


def test_tuned_shapes_key_plans_and_rebuild_once():
    """Two calibration profiles differing only in tuned shapes must compile
    to distinct plans (distinct ``plan.key``s), carry the tuned shapes, and
    each build programs exactly once — zero rebuilds on repeat."""
    a = planlib.compile_plan(PALL, N_STAGES, 64, 64)
    b = planlib.compile_plan(PALL._replace(head_tile=(16, 128)),
                             N_STAGES, 64, 64)
    c = planlib.compile_plan(PALL._replace(lane_block=(8, 256)),
                             N_STAGES, 64, 64)
    d = planlib.compile_plan(PALL._replace(head_rungs=HEAD_LADDER),
                             N_STAGES, 64, 64)
    assert len({a.key, b.key, c.key, d.key}) == 4
    assert b.head_tile == (16, 128) and c.lane_block == (8, 256)
    rng = np.random.default_rng(7)
    imgs = [render_scene(rng, 64, 64, n_faces=1)[0] for _ in range(3)]
    ref = None
    for cfg in (PALL, PALL._replace(head_tile=(16, 128),
                                    lane_block=(8, 256))):
        det = Detector(CASC, cfg)
        got = [det.detect(imgs[0]), det.detect_batch(imgs)]
        builds = det.program_builds
        assert builds > 0
        assert [np.asarray(r) for r in det.detect_batch(imgs)]
        det.detect(imgs[0])
        assert det.program_builds == builds       # zero rebuilds on repeat
        if ref is None:
            ref = got
        else:                                     # tuned shapes never
            assert np.array_equal(ref[0], got[0])  # change the bits
            for x, y in zip(ref[1], got[1]):
                assert np.array_equal(x, y)


# ------------------------------------------------- executor equivalence
def test_forced_rung_backends_bit_identical_end_to_end():
    """The same frames batched under ladders that force different
    backends at every tail rung must produce identical detections (the
    plan layer only changes *how* the tail runs, never what it computes)."""
    video = make_video("moving_face", n_frames=3, h=64, w=64, seed=4)
    frames = [f for f, _gt in video]
    ref = None
    for bk in ("gather", "bulk", "pallas"):
        ladder = ((10 ** 9, bk),)
        det = Detector(CASC, CFG._replace(tail_backend="auto",
                                          tail_rungs=ladder))
        plan = det.batch_plan(64, 64, len(frames))
        assert plan.tail_segments
        assert {s.backend for s in plan.tail_segments} == {bk}
        got = list(det.detect_batch(frames, strategy="packed"))
        if ref is None:
            ref = got
        else:
            for a, b in zip(ref, got):
                assert np.array_equal(a, b), bk


def test_forced_head_modes_bit_identical_end_to_end():
    """Forcing the dense head fused vs split must leave every executor's
    detections bit-identical — detect, both batch strategies, and the
    threshold-0 streaming path (the head mode only changes *how* the dense
    prefix runs, never what it computes)."""
    video = make_video("moving_face", n_frames=3, h=64, w=64, seed=4)
    rng = np.random.default_rng(11)
    imgs = [render_scene(rng, 64, 64, n_faces=1)[0] for _ in range(2)]
    ref = None
    for hm in ("split", "fused"):
        det = Detector(CASC, PALL._replace(head_mode=hm))
        vd = VideoDetector(det, StreamConfig(tile=16, threshold=0.0,
                                             keyframe_interval=0),
                           engine=StreamEngine(det))
        res = det.batch_result(imgs)
        got = ([det.detect(imgs[0])]
               + list(det.detect_batch(imgs, strategy="packed"))
               + list(det.detect_batch(imgs, strategy="vmap"))
               + [vd.process(f)[0] for f, _gt in video]
               + [np.asarray(res.alive_counts), _survivors(res)])
        if ref is None:
            ref = got
        else:
            for a, b in zip(ref, got):
                assert np.array_equal(a, b), hm


def _survivors(res) -> np.ndarray:
    """The pre-NMS survivors of a BatchResult, (image, level, y, x) rows."""
    val = np.asarray(res.valid)
    return np.stack([np.asarray(a)[val] for a in
                     (res.img, res.lvl, res.ys, res.xs)], axis=1)


def test_dense_mode_tile_exit_keeps_the_split_path_survivors():
    """Dense mode, the TPU default: the fused head runs every stage with
    its per-tile exit over a multi-level pyramid.  Per-stage alive counts,
    survivors and detections equal the split path's; the head's work
    counter reads the tiles that ran, as the oracle's tile exit over the
    dense sums counts them, and the split head reads dense work."""
    from repro.core.pyramid import downscale_indices
    from repro.kernels import ops, ref
    from repro.kernels.autotune import DEFAULT_TILE

    rng = np.random.default_rng(11)
    imgs = [render_scene(rng, 64, 64, n_faces=1)[0] for _ in range(2)]
    out = {}
    for hm in ("split", "fused"):
        det = Detector(CASC, PALL._replace(mode="dense", head_mode=hm))
        res = det.batch_result(imgs)
        out[hm] = (np.asarray(res.alive_counts), _survivors(res),
                   det.detect_batch(imgs), np.asarray(res.head_work))
    (counts, surv, rects, work), fused = out["split"], out["fused"]
    np.testing.assert_array_equal(fused[0], counts)
    np.testing.assert_array_equal(fused[1], surv)
    for a, b in zip(fused[2], rects):
        np.testing.assert_array_equal(a, b)
    assert counts[0].min() > counts[-1].max()      # the thresholds reject
    np.testing.assert_array_equal(work[:, 0], work[:, 1])
    np.testing.assert_array_equal(fused[3][:, 1], work[:, 1])

    # the counter, recounted from the dense sums with the oracle's exit
    plan = det.batch_plan(64, 64, len(imgs))
    stack = np.stack(imgs).astype(np.float32)
    n_weak = np.diff(np.asarray(CASC.stage_offsets))
    no_exit = CASC._replace(stage_threshold=np.full(N_STAGES, -np.inf,
                                                    np.float32))
    ty, tx = plan.head_tile or DEFAULT_TILE
    want = np.zeros(len(imgs), np.int64)
    for lp in plan.levels:
        img_l = stack[:, downscale_indices(64, lp.height)[:, None],
                      downscale_indices(64, lp.width)[None, :]]
        dense = ops.fused_head_batch(no_exit, CASC, 0, N_STAGES, img_l)[2]
        for i in range(len(imgs)):
            sums = ref.tile_exit_ref(dense[i], CASC.stage_threshold)
            ran = np.isfinite(np.asarray(sums)[:, ::ty, ::tx])
            want[i] += (ran.sum(axis=(1, 2)) * n_weak).sum()
    np.testing.assert_array_equal(fused[3][:, 0], want)
    assert (want < work[:, 1]).all()


def test_validate_config_through_plan():
    with pytest.raises(ValueError, match="compaction"):
        Detector(CASC, CFG._replace(capacity_fracs=(0.5, 0.5, 0.5, 0.5)))
    with pytest.raises(ValueError, match="tail_backend"):
        # repro: ignore[TAIL_BACKEND] negative test: exercises the unknown-backend rejection path
        Detector(CASC, CFG._replace(tail_backend="nope"))
    with pytest.raises(ValueError, match=r"\(0, 1\]"):
        n_comp = planlib.n_compactions(planlib.segment_spans(N_STAGES, CFG))
        Detector(CASC, CFG._replace(
            capacity_fracs=tuple([1.5] * n_comp)))


# --------------------------------------------------------------- serving
def test_service_work_units_read_off_plan():
    from repro.serve import DetectorService
    det = Detector(CASC, CFG._replace(pad_multiple=32))
    svc = DetectorService(det)
    units_small = svc._work_units((64, 64))
    units_big = svc._work_units((100, 90))
    assert units_small == det.batch_plan(64, 64).work_units
    assert units_big == det.batch_plan(128, 96).work_units
    assert units_big > units_small


def test_plan_work_units_weight_lanes_by_stage_depth():
    plan = planlib.compile_plan(CFG, N_STAGES, 64, 64, batch=1)
    per_seg = planlib.segment_work_units(plan)
    assert len(per_seg) == len(plan.segments)
    assert plan.work_units == sum(per_seg)
    dense_lanes = plan.n_slots * plan.batch
    for seg, units in zip(plan.segments, per_seg):
        lanes = dense_lanes if seg.dense else min(seg.capacity, dense_lanes)
        assert units == lanes * (seg.s1 - seg.s0)
        assert units > 0
    # stage-depth weighting: total work strictly exceeds the stage-1
    # window count whenever the cascade has more than one stage
    assert plan.work_units > plan.n_windows_total
    # batch scales every dense segment linearly
    plan2 = planlib.compile_plan(CFG, N_STAGES, 64, 64, batch=2)
    assert plan2.work_units > plan.work_units


def test_service_weighted_sharding_completes_all_items():
    from repro.serve import DetectorService, PodSpec, ServiceConfig
    det = Detector(CASC, CFG._replace(pad_multiple=32))
    svc = DetectorService(det, ServiceConfig(
        pods=(PodSpec("big", 1.0), PodSpec("little", 0.25))))
    rng = np.random.default_rng(3)
    shapes = [(64, 64), (90, 100), (64, 64), (70, 70), (64, 64)]
    imgs = [render_scene(rng, h, w, n_faces=1)[0] for h, w in shapes]
    got = svc.detect_many(imgs)
    for im, rects in zip(imgs, got):
        assert np.array_equal(rects, det.detect(im))
    st = svc.stats()
    assert sum(p.images for p in st.pods) == len(imgs)
    assert st.pods[0].images >= st.pods[1].images
