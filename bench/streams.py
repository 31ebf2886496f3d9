"""Parked cameras streamed through ``DetectorService``: the open-loop driver.

A network video recorder: ``cameras`` sessions (``open_stream``) with
device-resident state at threshold 0, fed by one generator on a fixed
schedule whether or not earlier frames have finished.  The aggregate rate
``rate_fps`` is split evenly over the cameras, whose frames are spaced evenly
inside each period.  Each camera's first frame, and a pre-roll that spreads
the cameras' keyframes evenly over the keyframe interval, fall in set-up; the
first camera's pre-roll runs through one keyframe, so the window runs no
program that set-up has not.  ``frame_latency_p95_ms`` is the 95th percentile
over every frame due in the window, each timed from when it was due.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import reference, scenes
from bench.harness import Outcome, span
from bench.photos import detector, memory_peak, same_rects


class RawRecorder:
    """Keeps, for each grouped rect array the program hands back, the raw
    rects (surviving windows) it grouped: the program's survivors of every
    frame, read where its decode meets its grouping."""

    def __init__(self):
        from repro.core import nms

        self.nms, self.group = nms, nms.group_rectangles
        self.raw: dict[int, tuple] = {}

        def recorded(rects, *a, **k):
            out = self.group(rects, *a, **k)
            self.raw[id(out)] = (out, np.array(rects, np.int64).reshape(-1, 4))
            return out

        nms.group_rectangles = recorded

    def close(self) -> None:
        self.nms.group_rectangles = self.group

    def of(self, rects) -> np.ndarray | None:
        hit = self.raw.get(id(rects))
        return hit[1] if hit is not None and hit[0] is rects else None


class Cameras:
    """The service, its camera sessions and where each camera's stream
    stands, after set-up."""

    def __init__(self, cfg: dict, arrays: dict, traffic: dict, seed: int):
        from repro.serve import DetectorService, PodSpec, ServiceConfig
        from repro.stream import StreamConfig

        self.det = detector(cfg, arrays)
        stream = StreamConfig(threshold=0.0, device_state=True,
                              keyframe_interval=traffic["keyframe_interval"])
        self.svc = DetectorService(self.det, ServiceConfig(
            pods=(PodSpec("chip0"),), max_batch=traffic["batch"],
            stream_config=stream))
        h, w = cfg["frame_hw"]
        lo, hi = traffic["faces"]
        self.cams = []
        # every seed watches the mix's scenes; the seed deals them to the
        # cameras, and so to the keyframe phases
        order = np.random.default_rng(seed).permutation(traffic["cameras"])
        for c in order:
            rng = np.random.default_rng([traffic["scene_seed"], int(c)])
            self.cams.append(scenes.ParkedCamera(
                int(rng.integers(2**62)), h, w, int(rng.integers(lo, hi + 1)),
                traffic["object"], traffic["move_px"], traffic["move_every"]))
        self.sessions = [self.svc.open_stream(stream) for _ in self.cams]
        self.next = self.preroll(traffic["keyframe_interval"])

    def preroll(self, interval: int) -> list[int]:
        """Each camera's first frame alone (a keyframe through the
        single-frame detect, as every keyframe of the window), then
        ``c * interval / n`` more frames for camera c, and a whole interval
        for camera 0, so its second keyframe runs here too.  Returns each
        camera's next frame."""
        svc, n = self.svc, len(self.cams)
        for s, cam in zip(self.sessions, self.cams):
            first = s.submit_frame(cam.frame(0))
            svc.flush()
            first.result()
        more = [interval] + [c * interval // n for c in range(1, n)]
        reqs = [s.submit_frame(cam.frame(t)) for s, cam, k in zip(
            self.sessions, self.cams, more) for t in range(1, k + 1)]
        svc.flush()
        for r in reqs:
            r.result()
        return [k + 1 for k in more]

    def window(self, rate: float, seconds: float, spans: bool) -> list:
        """Send every camera's frames on schedule for ``seconds``, then wait
        for them; returns (request, camera, frame index, due) per frame."""
        n = len(self.cams)
        period = n / rate
        sent = []
        t0 = time.perf_counter()
        k = 0
        while True:
            c, j = k % n, k // n
            due = t0 + j * period + c * period / n
            if due >= t0 + seconds:
                break
            wait = due - time.perf_counter()
            if wait > 0:
                with span("bench.sleep", spans):
                    time.sleep(wait)
            with span("bench.submit", spans):
                frame = self.cams[c].frame(self.next[c] + j)
                sent.append((self.sessions[c].submit_frame(frame), c,
                             self.next[c] + j, due))
            k += 1
        self.next = [x + (k + n - 1 - c) // n for c, x in enumerate(self.next)]
        t_sent = time.perf_counter()
        with span("bench.wait", spans):
            for r, *_ in sent:
                r.done.wait(max(60 - (time.perf_counter() - t_sent), 0))
        return sent


def summary(sent: list, rate: float, seconds: float, n: int) -> tuple:
    """(latency p95 in ms, the window's note) of one window's frames."""
    done = [x for x in sent if x[0].done.is_set() and x[0].error is None]
    lat = np.asarray([r.t_done - due for r, _c, _j, due in done])
    late = np.asarray([r.t_submit - due for r, _c, _j, due in sent])
    modes = [r.stats.mode for r, *_ in done]
    if not done:
        return float("inf"), f"window: none of {len(sent)} frames completed"
    p95 = float(np.percentile(lat, 95)) * 1e3
    q = max(len(lat) // 4, 1)
    note = (
        f"window: {len(sent)} frames due at {rate} frames/s over {n} "
        f"cameras in {seconds} s; completed {len(done)}; latency ms p50 "
        f"{np.percentile(lat, 50) * 1e3:.1f}, p95 {p95:.1f}, max "
        f"{lat.max() * 1e3:.1f}, median of the first quarter "
        f"{np.median(lat[:q]) * 1e3:.1f} and of the last "
        f"{np.median(lat[-q:]) * 1e3:.1f}; generator lateness ms p95 "
        f"{np.percentile(late, 95) * 1e3:.3f}, max {late.max() * 1e3:.3f}; "
        f"modes { {m: modes.count(m) for m in sorted(set(modes))} }")
    return p95, note


def run(cfg: dict, arrays: dict, traffic: dict, seed: int, seconds: float,
        tracer, t_process: float) -> Outcome:
    raw = RawRecorder()
    t_cams = time.perf_counter()
    rec = Cameras(cfg, arrays, traffic, seed)
    t_ready = time.perf_counter()
    svc = rec.svc
    builds0 = svc._program_build_count()
    spans = tracer is not None
    svc.start()
    if spans:
        tracer.start()
    window = span("bench.window", spans)
    window.__enter__()
    t0 = time.perf_counter()
    sent = rec.window(traffic["rate_fps"], seconds, spans)
    window.__exit__(None, None, None)
    rows = tracer.stop() if tracer is not None else None
    svc.stop()
    raw.close()
    builds = svc._program_build_count() - builds0

    out = Outcome(setup_s=t0 - t_process, attempted=len(sent))
    done = [x for x in sent if x[0].done.is_set() and x[0].error is None]
    out.failed = len(sent) - len(done)
    p95, note = summary(sent, traffic["rate_fps"], seconds, len(rec.cams))
    out.e2e["frame_latency_p95_ms"] = p95
    out.notes += [
        f"program builds inside the window: {builds}",
        f"set-up {out.setup_s:.3f} s: start-up and device "
        f"{t_cams - t_process:.3f} s, cameras, first frames and pre-roll "
        f"{t_ready - t_cams:.3f} s", note]
    out.ctx.update(trace_rows=rows, t0_host=t0,
                   frame_stats=[r.stats for r, *_ in done])
    out.memory_peak_bytes = memory_peak()
    served = [(r, rec.cams[c], j, raw.of(r.rects)) for r, c, j, _d in done]
    del rec, svc, raw
    gc.collect()
    out.checks = check(served, cfg, arrays, traffic, seed, out.failed)
    return out


def check(served, cfg, arrays, traffic, seed, failed: int) -> dict:
    """For a sample of the completed frames drawn from the seed: the raw
    rects (surviving windows) the program grouped for the frame against
    the reference's survivors of that frame, and the frame's rects against
    the reference's grouping of the program's raw rects."""
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(served), min(traffic["check_frames"], len(served)),
                      replace=False)
    levels = reference.pyramid(*reference.bucket(
        *cfg["frame_hw"], cfg["pad_multiple"]), cfg["scale_factor"])
    sym = union = rects_off = unread = 0
    for i in sorted(pick):
        r, cam, j, got = served[i]
        if got is None:
            unread += 1
            continue
        ref = reference.evaluate(cam.frame(j), arrays, cfg)
        want = set(map(tuple, reference.rects_of(ref.survivors,
                                                 levels).tolist()))
        have = set(map(tuple, got.tolist()))
        sym += len(have ^ want)
        union += len(have | want)
        rects_off += not same_rects(
            r.rects, reference.group(got, cfg["min_neighbors"]))
    return {
        "frames_failed": (failed, 0),
        "frames_unread": (unread + int(len(pick) == 0), 0),
        "survivors_off": (sym / max(union, 1),
                          cfg["limits"]["frame_survivors_off"]),
        "rects_off": (rects_off, 0),
    }
