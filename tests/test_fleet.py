"""Fleet-scale multi-tenant streaming: admission against the modeled
capacity budget, tier-ordered degradation (best_effort before standard,
realtime never), bit-identity of degraded threshold-0 sessions, co-keyed
sessions sharing one step program, and the serving API redesign's
compatibility shims (legacy kwargs construction, dict-key stats access)."""

import numpy as np
import pytest

from repro.core import Detector, EngineConfig, paper_shaped_cascade
from repro.serve import (DetectorService, FleetConfig, FleetScheduler,
                         PodSpec, ServiceConfig)
from repro.stream import StreamConfig, VideoDetector, make_video

CASC = paper_shaped_cascade(0, stage_sizes=[3, 4, 5, 6, 8])
KW = dict(step=2, scale_factor=1.3, min_neighbors=2)
HW = 64
SCFG = StreamConfig(tile=12, threshold=0.0, keyframe_interval=4,
                    degrade_keyframe_mult=2.0, max_degrade_level=3)


@pytest.fixture(scope="module")
def detector():
    return Detector(CASC, EngineConfig(mode="wave", pad_multiple=32, **KW))


def make_fleet(detector, capacity_mult=10.0, tiers=None, **fleet_kw):
    """A warmed-enough service + fleet whose capacity is an exact multiple
    of the HWxHW bucket's plan work-units/s (deterministic admission
    arithmetic, no wall-clock calibration)."""
    svc = DetectorService(detector, ServiceConfig(
        stream_config=SCFG, tier_slos=tiers or {}))
    units = svc._work_units((HW, HW))
    svc.seed_rates([capacity_mult * units])
    fleet = FleetScheduler(svc, FleetConfig(**fleet_kw))
    return svc, fleet, units


# ----------------------------------------------------------- admission
def test_admission_boundary_accept_then_reject(detector):
    # capacity = 2 plan-units/s -> budget = 0.85 * 2 = 1.7 plan-units/s
    svc, fleet, units = make_fleet(detector, capacity_mult=2.0)
    assert fleet.admit((HW, HW), fps=1.0, tier="standard") is not None
    # second identical stream would take modeled demand to 2.0 > 1.7
    assert fleet.admit((HW, HW), fps=1.0, tier="standard") is None
    # ... but a stream that fits the remaining 0.7 headroom is accepted
    assert fleet.admit((HW, HW), fps=0.5, tier="best_effort") is not None
    st = svc.stats().fleet
    assert (st.admitted, st.rejected, st.sessions) == (2, 1, 2)
    assert st.by_tier == {"standard": 1, "best_effort": 1}
    assert st.capacity_units_per_s == pytest.approx(2.0 * units)
    assert st.demand_units_per_s == pytest.approx(1.5 * units)
    assert st.plan_groups == 1            # same shape bucket -> one key


def test_fleet_requires_calibrated_capacity(detector):
    svc = DetectorService(detector, ServiceConfig(stream_config=SCFG))
    with pytest.raises(ValueError, match="capacity unknown"):
        FleetScheduler(svc)               # neither warmed nor seeded
    FleetScheduler(svc, capacity_units_per_s=100.0)   # explicit is fine


# ------------------------------------------------ tier-ordered ladder
def test_degradation_order_and_hysteresis_restore(detector):
    svc, fleet, units = make_fleet(detector, capacity_mult=4.0)
    rt = fleet.admit((HW, HW), fps=1.0, tier="realtime")
    st = fleet.admit((HW, HW), fps=1.0, tier="standard")
    be = fleet.admit((HW, HW), fps=1.0, tier="best_effort")
    assert None not in (rt, st, be)

    # push modeled demand over budget: every session claims full refreshes
    for s in (rt, st, be):
        s.note_work_frac(1.0)
    rt.fps = st.fps = be.fps = 1.6        # 4.8 units/s > 3.4 budget
    out = fleet.rebalance()
    assert out["degraded"] > 0
    # best_effort absorbs the whole ladder before standard is touched ...
    assert be.degrade_level > 0
    if st.degrade_level > 0:
        assert be.degrade_level == SCFG.max_degrade_level
    # ... and realtime is never degraded
    assert rt.degrade_level == 0
    # the degraded session's live config is its stretched base config
    assert (be.session.video.config
            == be.base_config.degraded(be.degrade_level))
    assert svc.stats().fleet.degrade_events == out["degraded"]

    # load falls away -> hysteresis restore brings every level back, one
    # ladder step per session per control-loop tick (no flapping jumps)
    rt.fps = st.fps = be.fps = 0.25
    restored = 0
    for _ in range(SCFG.max_degrade_level + 1):
        restored += fleet.rebalance()["restored"]
    assert restored > 0
    assert be.degrade_level == st.degrade_level == 0
    assert svc.stats().fleet.restore_events == restored


def test_shed_only_after_ladder_exhausted_and_only_best_effort(detector):
    svc, fleet, units = make_fleet(detector, capacity_mult=1.0)
    st = fleet.admit((HW, HW), fps=0.4, tier="standard")
    be = fleet.admit((HW, HW), fps=0.4, tier="best_effort")
    st.note_work_frac(1.0)
    be.note_work_frac(1.0)
    # 6 units/s vs 1 unit/s capacity: even the fully-degraded demand
    # (2 x 3.0 x 0.6^3 ~= 1.3 units/s) still exceeds capacity, so the
    # ladder alone cannot absorb this overload
    st.fps = be.fps = 3.0
    video = make_video("static_cctv", n_frames=1, h=HW, w=HW, seed=0)
    frame = video[0][0]

    # ladder not exhausted yet: nothing may be shed, only degraded
    req = fleet.submit_frame(be, frame)
    assert not req.dropped
    fleet.rebalance()                     # drives both to the ladder cap
    assert be.degrade_level == st.degrade_level == SCFG.max_degrade_level
    assert svc.stats().fleet.frames_dropped == 0

    # ladder exhausted and still over capacity: best_effort sheds ...
    req = fleet.submit_frame(be, frame)
    assert req.dropped and req.done.is_set()
    assert req.result().shape == (0, 4)
    # ... while standard frames keep flowing
    req2 = fleet.submit_frame(st, frame)
    assert not req2.dropped
    fs = svc.stats().fleet
    assert fs.frames_dropped == 1
    assert fs.frames_submitted == 3


# ------------------------------------------ degraded-config bit-identity
def test_degraded_session_bit_identical_to_fresh_stretched_config(detector):
    """Threshold-0 conservation survives the ladder: a session degraded
    *before* its first frame must produce exactly the frames a fresh
    VideoDetector configured with the same stretched config produces —
    and, at threshold 0, exactly per-frame ``detect``."""
    svc, fleet, _units = make_fleet(detector, capacity_mult=1.0)
    be = fleet.admit((HW, HW), fps=0.8, tier="best_effort")
    be.note_work_frac(1.0)
    be.fps = 4.0                          # far over budget
    fleet.rebalance()
    level = be.degrade_level
    assert level > 0
    assert be.session.video.config.keyframe_interval \
        > be.base_config.keyframe_interval
    # drop the offered rate (without rebalancing, so the degraded config
    # stays in force) — otherwise the exhausted ladder + over-capacity
    # demand would correctly shed these best-effort frames
    be.fps = 0.2

    video = make_video("static_cctv", n_frames=6, h=HW, w=HW, seed=3)
    ref = VideoDetector(detector, be.base_config.degraded(level))
    for frame, _gt in video:
        req = be.submit_frame(frame)
        fleet.flush()
        want, _st = ref.process(frame)
        got = req.result(timeout=60)
        assert np.array_equal(got, want)
        assert np.array_equal(got, detector.detect(frame))


def test_reconfigure_rejects_tile_change(detector):
    vd = VideoDetector(detector, SCFG)
    with pytest.raises(ValueError, match="tile"):
        vd.reconfigure(SCFG._replace(tile=20))
    vd.reconfigure(SCFG._replace(keyframe_interval=16))   # allowed
    assert vd.config.keyframe_interval == 16


def test_degraded_config_monotone_and_capped():
    cfg = StreamConfig(threshold=0.01, keyframe_interval=4,
                       degrade_keyframe_mult=2.0, degrade_threshold_add=0.005,
                       max_degrade_level=3)
    assert cfg.degraded(0) == cfg
    assert cfg.degraded(1).keyframe_interval == 8
    assert cfg.degraded(2).keyframe_interval == 16
    assert cfg.degraded(2).threshold == pytest.approx(0.02)
    assert cfg.degraded(99) == cfg.degraded(3)            # ladder cap
    # keyframe_interval == 0 means "never refresh" and must stay that way
    assert StreamConfig(keyframe_interval=0).degraded(2).keyframe_interval \
        == 0


# --------------------------------------------------- plan-key co-batching
def test_co_keyed_sessions_share_one_step_program(detector):
    """Two tenants on the same shape bucket run the same device step
    program, built once; warm rounds build no new programs, and every
    frame matches per-frame detect."""
    svc, fleet, _units = make_fleet(detector, capacity_mult=100.0)
    a = fleet.admit((96, 96), fps=1.0, tier="standard", tenant="a")
    b = fleet.admit((96, 96), fps=1.0, tier="standard", tenant="b")
    vids = [make_video("static_cctv", n_frames=4, h=96, w=96, seed=s)
            for s in (0, 1)]
    modes = []
    for t in range(4):
        reqs = [s.submit_frame(v[t][0]) for s, v in zip((a, b), vids)]
        if t == 3:
            builds0 = svc._program_build_count()
        fleet.flush()
        if t == 3:   # warm round: the co-keyed flush compiled nothing new
            assert svc._program_build_count() == builds0
        for r, v in zip(reqs, vids):
            assert np.array_equal(r.result(timeout=60),
                                  detector.detect(v[t][0]))
            modes.append(r.stats.mode)
    assert svc.stream_engine.program_builds == 1
    assert "incremental" in modes
    assert svc.stats().fleet.plan_groups == 1


# ------------------------------------------------- API-redesign shims
def test_legacy_kwargs_construction_warns_and_works(detector):
    with pytest.warns(DeprecationWarning, match="ServiceConfig"):
        svc = DetectorService(detector, pods=(PodSpec("big", 1.0),),
                              max_batch=4, slo_ms=75.0)
    assert svc.config == ServiceConfig(pods=(PodSpec("big", 1.0),),
                                       max_batch=4, slo_ms=75.0)
    assert svc.max_batch == 4 and svc.slo_ms == 75.0
    with pytest.raises(TypeError, match="not both"):
        DetectorService(detector, ServiceConfig(), max_batch=4)
    with pytest.raises(ValueError):
        ServiceConfig(batch_sizes=())
    with pytest.raises(ValueError):
        ServiceConfig(tier_slos={"gold": 10.0})
    with pytest.raises(ValueError):
        ServiceConfig(tier_slos={"realtime": -1.0})


def test_stats_dict_shim_matches_typed_fields(detector):
    svc = DetectorService(detector)
    st = svc.stats()
    assert st.schema_version == 1
    with pytest.warns(DeprecationWarning, match="deprecated"):
        assert st["n_done"] == st.n_done
    with pytest.warns(DeprecationWarning):
        assert st["stream"]["sessions"] == st.stream.sessions
    with pytest.warns(DeprecationWarning):
        assert st["energy"] == {"governor": None}     # historical stanza
    d = st.as_dict()                                  # JSON contract
    assert d["schema_version"] == 1 and d["fleet"] is None
    assert set(d) >= {"n_done", "imgs_per_s", "tail", "pods", "stream",
                      "energy", "latency_ms_p50", "latency_ms_p95"}


def test_tier_validation_on_submit_and_open_stream(detector):
    svc = DetectorService(detector)
    with pytest.raises(ValueError, match="tier"):
        svc.submit(np.zeros((HW, HW), np.float32), tier="gold")
    with pytest.raises(ValueError, match="tier"):
        svc.open_stream(tier="gold")
    sess = svc.open_stream(tier="realtime")
    assert sess.tier == "realtime"
