"""Micro-batching detector service: queue -> bucket -> pod shard ->
``detect_batch`` -> per-request decode, plus the rate-weighted pod
scheduling loop (calibration, EMA rate tracking, straggler replanning)."""

import time

import numpy as np
import pytest

from repro.core import Detector, EngineConfig, paper_shaped_cascade
from repro.core.training.data import render_scene
from repro.scheduling.hetero import rate_weighted_split, update_rates_ema
from repro.serve import DetectorService, PodSpec, ServiceConfig

CASC = paper_shaped_cascade(0, stage_sizes=[3, 4, 5, 6, 8])
KW = dict(step=2, scale_factor=1.3, min_neighbors=2)


@pytest.fixture(scope="module")
def detector():
    return Detector(CASC, EngineConfig(mode="wave", pad_multiple=32, **KW))


@pytest.fixture(scope="module")
def images():
    rng = np.random.default_rng(9)
    shapes = [(64, 64), (64, 64), (70, 90), (100, 60), (64, 64)]
    return [render_scene(rng, h, w, n_faces=1)[0] for h, w in shapes]


def test_detect_many_matches_detect(detector, images):
    svc = DetectorService(detector, ServiceConfig(
        pods=(PodSpec("big", 1.0), PodSpec("little", 0.4))))
    got = svc.detect_many(images)
    for im, rects in zip(images, got):
        assert np.array_equal(rects, detector.detect(im))


def test_submit_flush_futures(detector, images):
    svc = DetectorService(detector)
    reqs = [svc.submit(im) for im in images[:3]]
    assert all(not r.done.is_set() for r in reqs)
    n = svc.flush()
    assert n == 3
    for im, r in zip(images, reqs):
        assert r.done.is_set()
        assert r.latency_s >= 0
        assert np.array_equal(r.result(), detector.detect(im))
    assert svc.flush() == 0                   # queue drained


def test_chunking_bounded_batch_shapes(detector):
    svc = DetectorService(detector,
                          ServiceConfig(batch_sizes=(1, 2, 4), max_batch=4))
    shard = list(range(7))
    sizes = [len(c) for c in svc._chunks(shard)]
    assert sizes == [4, 2, 1]
    assert sum(sizes) == 7


def test_pod_shares_and_stats(detector, images):
    svc = DetectorService(detector, ServiceConfig(
        pods=(PodSpec("big", 1.0), PodSpec("little", 0.25))))
    svc.detect_many(images)
    st = svc.stats()
    assert st.n_done == len(images)
    assert sum(p.images for p in st.pods) == len(images)
    # rate-weighted: the big pod must get at least as much as the LITTLE one
    big, little = st.pods
    assert big.images >= little.images
    assert st.latency_ms_p95 >= st.latency_ms_p50 >= 0
    assert st.imgs_per_s > 0


def test_warmup_calibrates_without_changing_results(detector, images):
    svc = DetectorService(detector)
    base = detector.detect(images[0])
    svc.warmup(images[0])
    assert svc.detector.config.capacity_fracs     # profile-guided
    assert np.array_equal(svc.detector.detect(images[0]), base)
    got = svc.detect_many(images[:2])
    for im, rects in zip(images, got):
        assert np.array_equal(rects, detector.detect(im))


def test_overflow_isolated_per_request(images):
    """A batch in which every window survives (overflow) degrades to
    per-image detection instead of failing the whole flush."""
    from helpers import all_pass_cascade
    det = Detector(all_pass_cascade(),
                   EngineConfig(mode="wave", step=1, scale_factor=2.0,
                                batch_capacity_fracs=(0.01,),
                                capacity_fracs=(1.0,)))
    svc = DetectorService(det)
    imgs = [np.zeros((96, 96), np.float32)] * 2
    got = svc.detect_many(imgs)               # falls back to per-image path
    for rects, im in zip(got, imgs):
        assert np.array_equal(rects, det.detect(im))


def test_background_thread_flushes(detector, images):
    svc = DetectorService(detector,
                          ServiceConfig(max_batch=2, max_delay_ms=10.0))
    svc.start()
    try:
        reqs = [svc.submit(im) for im in images[:2]]
        for r in reqs:
            r.result(timeout=30.0)
    finally:
        svc.stop()
    assert svc.stats().n_done >= 2


# ------------------------------------------------------------- scheduling
def test_rate_update_and_replan():
    svc_rates = np.asarray([10.0, 10.0])
    new = update_rates_ema(svc_rates, np.asarray([30.0, 0.0]), alpha=0.5)
    assert new[0] == 20.0 and new[1] == 10.0  # idle pod keeps its rate

    plan = rate_weighted_split(8, [1.0, 1.0], ["big", "little"])
    assert plan.shares == (4, 4)
    skew = rate_weighted_split(8, [3.0, 1.0], ["big", "little"])
    assert skew.shares == (6, 2)
    assert skew.imbalance == pytest.approx(1.0)


def test_imbalance_infinite_when_loaded_pod_has_zero_rate():
    """Regression: a pod whose measured rate collapsed to 0 while still
    holding work never finishes — imbalance must be inf, not a silently
    dropped term that makes the plan look balanced."""
    from repro.scheduling.hetero import HeteroPodPlan
    dead = HeteroPodPlan(("big", "little"), (1.0, 0.0), (4, 4))
    assert dead.imbalance == float("inf")
    # ... but a zero-rate pod with zero share is fine (it was parked)
    parked = HeteroPodPlan(("big", "little"), (1.0, 0.0), (8, 0))
    assert np.isfinite(parked.imbalance)
    assert parked.imbalance == pytest.approx(1.0)
    # degenerate no-work plan stays defined
    empty = HeteroPodPlan(("big",), (0.0,), (0,))
    assert empty.imbalance == pytest.approx(1.0)


def test_first_flush_compile_wall_does_not_poison_rates(detector):
    """Regression: the first flush of a new batch shape pays jit
    trace/compile inside the measured wall.  That observation must be
    discarded — only warm walls may move the rate EMA."""
    svc = DetectorService(detector, ServiceConfig(
        pods=(PodSpec("big", 1.0), PodSpec("little", 0.5)), rate_ema=0.5))
    items = list(range(8))
    weights = [10] * len(items)

    def compiling_run(shard):
        svc.detector.program_builds += 1     # first-touch program build
        time.sleep(0.02)                     # the compile wall

    before = svc._rates.copy()
    svc._shard_across_pods(items, compiling_run, weights)
    assert np.array_equal(svc._rates, before)       # discarded
    assert not svc._rates_in_units

    def warm_run(shard):
        time.sleep(0.002)

    svc._shard_across_pods(items, warm_run, weights)
    assert svc._rates_in_units                      # first warm wall lands
    first = svc._rates.copy()
    assert (first > 0).all()
    for _ in range(3):                              # stable under repeats
        svc._shard_across_pods(items, warm_run, weights)
    assert ((svc._rates > first * 0.2) & (svc._rates < first * 5)).all()


def test_service_replans_on_straggle(detector, images):
    svc = DetectorService(detector, ServiceConfig(
        pods=(PodSpec("big", 1.0), PodSpec("little", 0.1)),
        rate_ema=1.0, replan_threshold=0.05))
    for _ in range(3):
        svc.detect_many(images[:4])
    st = svc.stats()
    # measured rates diverge strongly from the 10:1 nominal guess at least
    # once, so the straggle replanner must have fired
    assert st.replans >= 1
    assert st.pods[0].rate != st.pods[1].rate


def test_program_errors_propagate_from_flush(detector, images, monkeypatch):
    """Only capacity overflow is isolated per request: any other error of
    the batch program (a compile or runtime failure) fails the flush
    instead of being retried image by image."""
    svc = DetectorService(detector)

    def broken(*_a, **_k):
        raise ValueError("program failed to compile")

    monkeypatch.setattr(svc.detector, "detect_batch", broken)
    monkeypatch.setattr(svc.detector, "detect", broken)
    svc.submit(images[0])
    with pytest.raises(ValueError, match="failed to compile"):
        svc.flush()
