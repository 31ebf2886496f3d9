#!/usr/bin/env python3
"""Bring-up smoke run of the served face-detection cascade on one TPU chip.

    python chip_smoke.py

Drives the served path once through its user entry points, at full width:
the paper-shaped cascade (25 stages, 2,913 weak classifiers) at step 1 and
scale 1.2 on seeded 640x480 uint8 scenes, with the Pallas kernels compiled
by Mosaic.  Phases, each raising on failure:

1. device check — exits non-zero, printing the platform found, unless
   JAX's devices are TPUs;
2. one-shot serving — ``DetectorService.warmup`` then ``detect_many`` on 8
   scenes; every request completes and the batch program holds a Mosaic
   kernel (``tpu_custom_call``);
3. oracle agreement — the same scenes through ``use_pallas=False`` (the
   jnp path), for the paper cascade and for the pretrained cascade, which
   finds the planted faces: grouped rects must match, and the pre-NMS
   survivor mismatch counts are printed;
4. device-resident stream — 8 ``intermittent_cctv`` frames through
   ``VideoDetector.submit``/``retire``, each equal to ``Detector.detect``.

The seconds it prints are smoke timings of one run, not benchmark
metrics.  The last line of its output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
H, W = 480, 640                 # VGA camera frames
N_IMAGES = 8                    # one-shot requests
N_FRAMES = 8                    # stream frames
ENGINE = dict(step=1, scale_factor=1.2, pad_multiple=32)


def device_check() -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{d.platform!r} ({len(devs)} x {d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _survivors(res) -> set:
    val = np.asarray(res.valid)
    return set(zip(*(np.asarray(a)[val].tolist()
                     for a in (res.img, res.lvl, res.ys, res.xs))))


def densest(det, frames) -> int:
    """Index of the frame that keeps the most windows alive through the
    whole cascade (``det`` uncalibrated, so no capacity truncates the
    counts).  Warming up on it sizes the capacities for every frame."""
    alive = [sum(int(np.asarray(res.alive_counts)[-1])
                 for res, _scale in det.detect_raw(f)) for f in frames]
    return int(np.argmax(alive))


def one_shot(cascade, frames):
    """Serve ``frames`` through the service; returns (detector, rects)."""
    from repro.core import Detector, EngineConfig
    from repro.serve import DetectorService, PodSpec, ServiceConfig

    det = Detector(cascade, EngineConfig(**ENGINE))
    if not det.config.use_pallas:
        raise SystemExit("chip_smoke: the platform default left the Pallas "
                         "kernels off")
    svc = DetectorService(det, ServiceConfig(pods=(PodSpec("tpu0", 1.0),)))
    t0 = time.perf_counter()
    probe = densest(det, frames)
    svc.warmup(frames[probe])
    reqs = [svc.submit(f) for f in frames]
    svc.flush()
    served = [r.result() for r in reqs]      # raises a request's error
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = svc.detect_many(frames)
    warm_s = (time.perf_counter() - t0) / len(frames)
    if not all(np.array_equal(a, b) for a, b in zip(served, again)):
        raise SystemExit("chip_smoke: a warm repeat changed the rects")

    det = svc.detector                       # the calibrated detector
    plan = det.batch_plan(H, W, len(frames))
    print(f"one-shot: {len(reqs)} requests of {H}x{W} served, "
          f"{cascade.n_stages} stages / {cascade.n_weak} weak classifiers, "
          f"step {det.config.step}, scale {det.config.scale_factor}, "
          f"mode {det.config.mode}, warmed up on frame {probe}; "
          f"rects per image {[len(r) for r in served]}")
    print(f"one-shot: batch program head modes {sorted(set(plan.head_modes))}"
          f", tail backends {[s.backend for s in plan.tail_segments]}")
    print(f"one-shot smoke timings (one run, not benchmark metrics): "
          f"set-up incl. compile {setup_s:.1f} s, warm "
          f"{warm_s * 1e3:.1f} ms per request")
    return det, served


def mosaic_kernels(det, n: int) -> int:
    """``tpu_custom_call`` ops (Mosaic kernels) in the lowered batch
    program that ``detect_batch`` runs for ``n`` frames."""
    import jax
    import jax.numpy as jnp

    plan = det.batch_plan(H, W, n)
    head, tail = det.batch_parts(plan.hp, plan.wp, n)
    hlo = jax.jit(lambda c, s, v: tail(c, *head(c, s, v))).lower(
        det.cascade, jax.ShapeDtypeStruct((n, plan.hp, plan.wp), jnp.float32),
        jax.ShapeDtypeStruct((n, 2), jnp.int32)).as_text()
    return hlo.count("tpu_custom_call")


def oracle_agreement(label: str, det, frames, rects) -> None:
    """Compare ``det`` (Pallas) with its jnp twin on ``frames``."""
    from repro.core import Detector

    oracle = Detector(det.cascade, det.config._replace(use_pallas=False))
    got, want = det.batch_result(frames), oracle.batch_result(frames)
    counts_diff = int((np.asarray(got.alive_counts)
                       != np.asarray(want.alive_counts)).sum())
    surv_got, surv_want = _survivors(got), _survivors(want)
    ref = oracle.detect_batch(frames)
    rect_diff = sum(not np.array_equal(a, b) for a, b in zip(rects, ref))
    print(f"oracle[{label}]: survivors {len(surv_got)} vs "
          f"{len(surv_want)}, {len(surv_got ^ surv_want)} differ; "
          f"{counts_diff} per-stage alive counts differ; grouped rects "
          f"{sum(map(len, rects))} vs {sum(map(len, ref))}, "
          f"{rect_diff} image(s) differ")
    if rect_diff:
        raise SystemExit(f"chip_smoke: {label} grouped rects differ from "
                         f"the use_pallas=False path on {rect_diff} image(s)")


def stream(det, frames) -> None:
    """Device-resident stream over ``frames``; each equals ``detect``."""
    from repro.stream import StreamConfig, VideoDetector

    vid = VideoDetector(det, StreamConfig(threshold=0.0, device_state=True))
    t0 = time.perf_counter()
    toks = [vid.submit(f) for f in frames]
    outs = [vid.retire(t) for t in toks]
    run_s = time.perf_counter() - t0
    modes = [st.mode for _rects, st in outs]
    bad = [i for i, (f, (rects, _st)) in enumerate(zip(frames, outs))
           if not np.array_equal(rects, det.detect(f))]
    print(f"stream: {len(frames)} device-resident {H}x{W} frames, modes "
          f"{modes}, rects per frame {[len(r) for r, _st in outs]}; "
          f"{run_s:.1f} s incl. compile (smoke timing)")
    if bad:
        raise SystemExit(f"chip_smoke: stream frames {bad} differ from "
                         f"per-frame detect")


def main() -> None:
    sys.stdout.reconfigure(line_buffering=True)
    device = device_check()
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.compile_cache import use_compile_cache
    from repro.configs.viola_jones import paper_cascade, pretrained
    from repro.core import Detector, EngineConfig
    from repro.stream import make_video
    from benchmarks.common import corpus

    print(f"device: {device['kind']} x{device['count']} "
          f"({device['platform']}); compile cache: {use_compile_cache()}")
    t_start = time.perf_counter()

    def phase(name: str, t0: float) -> float:
        now = time.perf_counter()
        print(f"phase {name}: {now - t0:.1f} s (smoke timing, incl. "
              f"compile; {now - t_start:.1f} s so far)")
        return now

    t = time.perf_counter()
    frames = [img for img, _boxes in corpus(N_IMAGES, H, W, seed=0)]
    det, rects = one_shot(paper_cascade(), frames)
    n_kernels = mosaic_kernels(det, len(frames))
    print(f"one-shot: {n_kernels} tpu_custom_call op(s) in the batch program")
    if n_kernels == 0:
        raise SystemExit("chip_smoke: no tpu_custom_call in the batch "
                         "program: no Mosaic kernel ran")
    t = phase("one-shot serving", t)
    oracle_agreement("paper", det, frames, rects)
    t = phase("oracle agreement, paper cascade", t)

    pre = Detector(pretrained()[0], EngineConfig(**ENGINE))
    pre = pre.calibrated(frames[densest(pre, frames)])
    oracle_agreement("pretrained", pre, frames, pre.detect_batch(frames))
    t = phase("oracle agreement, pretrained cascade", t)

    stream(det, [f for f, _boxes in make_video(
        "intermittent_cctv", n_frames=N_FRAMES, h=H, w=W)])
    phase("device-resident stream", t)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
