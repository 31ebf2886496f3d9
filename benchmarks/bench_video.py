"""Streaming video detection: temporal tile-reuse vs per-frame detection.

Four synthetic scenarios spanning the temporal-locality spectrum (see
`repro.stream.synthetic`): mostly-static CCTV (the streaming win), a
moving face, slow lighting drift under a positive threshold, and a camera
pan (the adversarial bound — everything changes, streaming must degrade to
roughly per-frame cost, not collapse).

Each stream runs the device-resident step, double-buffered across frames.
Reported per scenario: per-frame baseline vs streaming throughput, frame
latency percentiles, the fraction of tiles/windows skipped, and — for
threshold-0 scenarios — whether the streaming output was bit-identical to
``Detector.detect`` on every frame (it must be; the equivalence suite in
``tests/test_stream.py`` enforces the same invariant).  Timings are of
whatever backend runs the benchmark."""

from __future__ import annotations

import time

import numpy as np

from .common import save_rows, print_table, pretrained_cascade

SCENARIOS = [
    # (name, threshold, tile, keyframe_interval)
    ("static_cctv", 0.0, 16, 0),
    ("intermittent_cctv", 0.0, 16, 0),
    ("moving_face", 0.0, 16, 0),
    ("lighting_drift", 4.0, 16, 8),
    ("camera_pan", 0.0, 16, 0),
]


REPEATS = 3    # timed passes per row; min-time is the low-noise estimator


def _run_scenario(det, engine, kind, threshold, tile, keyframe, n_frames,
                  hw):
    from repro.stream import VideoDetector, StreamConfig, make_video

    video = make_video(kind, n_frames=n_frames, h=hw, w=hw, seed=3)
    frames = [f for f, _gt in video]
    cfg = StreamConfig(tile=tile, threshold=threshold,
                       keyframe_interval=keyframe)

    # warm the detector and the stream step (compile; the engine's jit
    # cache is shared) through the pipelined loop the timed run uses
    det.detect(frames[0])
    warm = VideoDetector(det, cfg, engine=engine)
    prev = None
    for f in frames:
        tok = warm.submit(f)
        if prev is not None:
            warm.retire(prev)
        prev = tok
    warm.retire(prev)

    # each timed pass measures baseline and stream back to back on a fresh
    # VideoDetector; the per-path minimum over the repeats strips scheduler
    # noise from the speedup ratio
    base_s = stream_s = None
    exact = True
    builds0 = engine.program_builds + det.program_builds
    for _rep in range(REPEATS):
        t0 = time.perf_counter()
        baseline = [det.detect(f) for f in frames]
        base_s = (time.perf_counter() - t0 if base_s is None
                  else min(base_s, time.perf_counter() - t0))

        vd = VideoDetector(det, cfg, engine=engine)
        rep_plan, rep_commit, rep_stats, streamed = [], [], [], []
        t0 = time.perf_counter()
        # depth-2 double-buffered loop: frame N+1's plan-and-eval step is
        # dispatched before frame N's result is fetched
        prev = None
        for f in frames:
            t1 = time.perf_counter()
            tok = vd.submit(f)
            rep_plan.append(time.perf_counter() - t1)
            if prev is not None:
                t1 = time.perf_counter()
                rects, st = vd.retire(prev)
                rep_commit.append(time.perf_counter() - t1)
                streamed.append(rects)
                rep_stats.append(st)
            prev = tok
        t1 = time.perf_counter()
        rects, st = vd.retire(prev)
        rep_commit.append(time.perf_counter() - t1)
        streamed.append(rects)
        rep_stats.append(st)
        rep_s = time.perf_counter() - t0
        exact = exact and all(np.array_equal(a, b)
                              for a, b in zip(baseline, streamed))
        if stream_s is None or rep_s < stream_s:
            stream_s, plan_t, commit_t = rep_s, rep_plan, rep_commit
            stats, xfer = rep_stats, vd.xfer_bytes
    # programs compiled during the *timed* (pre-warmed) passes: a plan-cache
    # regression shows up here as a nonzero rebuild count in the artifact
    rebuilds = engine.program_builds + det.program_builds - builds0

    lat_ms = (np.asarray(plan_t) + np.asarray(commit_t)) * 1e3
    # fraction of pyramid levels whose head ran per frame (after the
    # first keyframe): the step's level-skip metric
    lvl_sat = float(np.mean([s.levels_active / max(s.levels_total, 1)
                             for s in stats[1:]])) if len(stats) > 1 else 1.0
    return {
        "scenario": kind,
        "threshold": threshold,
        "frames": n_frames,
        "base_fps": n_frames / base_s,
        "stream_fps": n_frames / stream_s,
        "speedup": base_s / stream_s,
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "p95_ms": float(np.percentile(lat_ms, 95)),
        # phase split: submit (async dispatch) vs retire (sync + decode)
        "plan_ms": float(np.mean(plan_t) * 1e3),
        "commit_ms": float(np.mean(commit_t) * 1e3),
        # host<->device traffic per frame (accounted, not measured)
        "host_xfer": int(xfer / n_frames),
        "tile_skip": float(np.mean([s.tile_skip_frac for s in stats])),
        "window_skip": float(np.mean([s.window_skip_frac for s in stats])),
        "lvl_sat_frac": lvl_sat,
        "modes": "/".join(f"{m}:{sum(1 for s in stats if s.mode == m)}"
                          for m in ("full", "incremental", "cached")),
        "exact": exact if threshold <= 0 else "-",
        "programs": engine.program_builds,
        "rebuilds": rebuilds,
    }


def run(n_frames: int = 24, hw: int = 160, fast: bool = False) -> list[dict]:
    from repro.core import Detector, EngineConfig

    if fast:
        n_frames, hw = 24, 160
    casc, _ = pretrained_cascade()
    det = Detector(casc, EngineConfig(mode="wave", step=2,
                                      scale_factor=1.25, min_neighbors=2))
    from repro.stream import make_video, StreamEngine
    probe = make_video("static_cctv", n_frames=1, h=hw, w=hw, seed=3)[0][0]
    det = det.calibrated(probe)
    engine = StreamEngine(det)
    return [_run_scenario(det, engine, kind, threshold, tile, keyframe,
                          n_frames, hw)
            for kind, threshold, tile, keyframe in SCENARIOS]


def main(fast: bool = False):
    rows = run(fast=fast)
    print_table(rows)
    save_rows("bench_video", rows)
    cctv = rows[0]
    assert cctv["exact"] is True, "threshold-0 streaming must be bit-exact"
    assert cctv["rebuilds"] == 0, (
        f"warmed static stream rebuilt {cctv['rebuilds']} program(s) — "
        f"plan cache regression")
    if cctv["speedup"] < 2.0:
        print(f"WARNING: static-stream speedup {cctv['speedup']:.2f}x < 2x")
    inter = rows[1]
    assert inter["exact"] is True, "threshold-0 streaming must be bit-exact"
    assert inter["lvl_sat_frac"] < 0.5, (
        f"mostly-idle stream should run the head on < 50% of pyramid "
        f"levels per frame, got {inter['lvl_sat_frac']:.2f}")
    for r in rows:
        if r["threshold"] <= 0:
            assert r["exact"] is True, (
                f"stream must stay bit-exact at threshold 0: "
                f"{r['scenario']}")
        assert r["rebuilds"] == 0, (
            f"warmed stream rebuilt {r['rebuilds']} program(s): "
            f"{r['scenario']}")
    return rows


if __name__ == "__main__":
    main(fast=True)
