"""Parked cameras streamed through ``DetectorService``: the closed-loop driver.

A video-management system indexing, or catching up on, recorded footage of
parked cameras as fast as the chip allows: ``cameras`` sessions
(``open_stream``) with device-resident state at threshold 0, each keeping
``in_flight`` frames submitted and not completed, and submitting its next
frame when one completes.  Set-up is the cameras of ``bench/streams.py``
(``Cameras``: each camera's first frame, then a pre-roll that spreads the
keyframes over the keyframe interval and runs camera 0 through a second
keyframe), so the window runs no program that set-up has not.

``images_per_s`` counts the frames completed after the window's first
completion, up to the last completion of a frame submitted inside
``--seconds``, over the time between those two completions.  A set-up that
has not finished ``SETUP_DEADLINE_S`` seconds after the process started
ends the process with an error that names the set-up.  The configuration's
``stream`` states the sessions' settings; ``Cameras`` opens every session
with ``SESSIONS``, and a configuration that states others is refused.
"""

from __future__ import annotations

import gc
import os
import sys
import threading
import time
from collections import deque

from bench.harness import Outcome, span
from bench.photos import memory_peak
from bench.streams import Cameras, RawRecorder, check

SETUP_DEADLINE_S = 600.0
SESSIONS = {"threshold": 0.0, "device_state": True}


class Watchdog:
    """Ends the process, naming the set-up, if ``done`` is not called
    within ``deadline_s`` of ``t_start`` (a ``perf_counter`` time).
    ``on_fire`` replaces the exit where a caller must stay alive."""

    def __init__(self, t_start: float, deadline_s: float, on_fire=None):
        self.deadline_s = deadline_s
        self.on_fire = on_fire or self._exit
        left = max(t_start + deadline_s - time.perf_counter(), 0.0)
        self.timer = threading.Timer(left, self._fire)
        self.timer.daemon = True

    def start(self) -> "Watchdog":
        self.timer.start()
        return self

    def done(self) -> None:
        self.timer.cancel()

    def message(self) -> str:
        return (f"bench: set-up did not finish within {self.deadline_s:g} s "
                "of the process start (building the detector, compiling the "
                "stream step and the cameras' first frames and pre-roll)")

    def _fire(self) -> None:
        self.on_fire(self.message())

    @staticmethod
    def _exit(msg: str) -> None:
        sys.stderr.write(msg + "\n")
        sys.stderr.flush()
        os._exit(3)


def needs_device_keyframes() -> None:
    """Exit with an error naming the set-up unless the program's device
    stream step runs every frame, keyframes included, in one program and
    reports the head's work (``StreamStepOut.head_tiles``): a step that
    still regrows capacity rungs and sends keyframes to the host cannot
    finish this set-up in time."""
    from repro.stream.engine import StreamStepOut

    if "head_tiles" not in StreamStepOut._fields:
        sys.stderr.write(
            "bench: set-up cannot finish: this cell needs a device stream "
            "step that commits every frame, keyframes included, in one "
            "program and reports its head work (StreamStepOut.head_tiles); "
            "this program's step has fields "
            f"{list(StreamStepOut._fields)}\n")
        raise SystemExit(3)


def window(rec: Cameras, seconds: float, in_flight: int, spans: bool
           ) -> list:
    """Keep ``in_flight`` frames of every camera submitted and not
    completed for ``seconds``, then wait for the last of them; returns
    (request, camera, frame index) per frame, in submit order."""
    n = len(rec.cams)
    pending = [deque() for _ in range(n)]
    sent = []
    deadline = time.perf_counter() + seconds
    while True:
        now = time.perf_counter()
        for c in range(n):
            while pending[c] and pending[c][0].done.is_set():
                pending[c].popleft()
            while now < deadline and len(pending[c]) < in_flight:
                with span("bench.submit", spans):
                    j = rec.next[c]
                    req = rec.sessions[c].submit_frame(rec.cams[c].frame(j))
                rec.next[c] = j + 1
                pending[c].append(req)
                sent.append((req, c, j))
        waiting = [p[0] for p in pending if p]
        if not waiting:
            return sent
        oldest = min(waiting, key=lambda r: r.t_submit)
        with span("bench.wait", spans):
            oldest.done.wait(max(min(deadline - now, 0.05), 0.001)
                             if now < deadline else 60.0)


def run(cfg: dict, arrays: dict, traffic: dict, seed: int, seconds: float,
        tracer, t_process: float,
        deadline_s: float = SETUP_DEADLINE_S) -> Outcome:
    if cfg.get("stream", SESSIONS) != SESSIONS:
        raise SystemExit(f"bench: {cfg['name']} states sessions "
                         f"{cfg['stream']}; this driver opens {SESSIONS}")
    watchdog = Watchdog(t_process, deadline_s).start()
    needs_device_keyframes()
    raw = RawRecorder()
    t_cams = time.perf_counter()
    rec = Cameras(cfg, arrays, traffic, seed)
    t_ready = time.perf_counter()
    watchdog.done()
    svc = rec.svc
    builds0 = svc._program_build_count()
    spans = tracer is not None
    svc.start()
    if spans:
        tracer.start()
    win = span("bench.window", spans)
    win.__enter__()
    t0 = time.perf_counter()
    sent = window(rec, seconds, traffic["in_flight"], spans)
    win.__exit__(None, None, None)
    rows = tracer.stop() if tracer is not None else None
    svc.stop()
    raw.close()
    builds = svc._program_build_count() - builds0

    out = Outcome(setup_s=t0 - t_process, attempted=len(sent))
    done = [x for x in sent if x[0].done.is_set() and x[0].error is None]
    out.failed = len(sent) - len(done)
    ends = sorted(r.t_done for r, *_ in done)
    if len(ends) < 2:
        raise RuntimeError(f"{len(ends)} frame(s) completed in a window of "
                           f"{seconds} s; the throughput needs two")
    span_s = ends[-1] - ends[0]
    out.e2e["images_per_s"] = (len(ends) - 1) / span_s
    modes = [r.stats.mode for r, *_ in done]
    out.notes += [
        f"program builds inside the window: {builds}",
        f"set-up {out.setup_s:.3f} s: start-up and device "
        f"{t_cams - t_process:.3f} s, cameras, first frames and pre-roll "
        f"{t_ready - t_cams:.3f} s",
        f"window: {len(sent)} frames submitted over {len(rec.cams)} cameras "
        f"with {traffic['in_flight']} in flight each in {seconds} s; "
        f"{len(ends) - 1} completed in the {span_s:.3f} s after the first "
        f"completion; failed {out.failed}; modes "
        f"{ {m: modes.count(m) for m in sorted(set(modes))} }"]
    out.ctx.update(trace_rows=rows, t0_host=t0, arrays=arrays,
                   frame_stats=[r.stats for r, *_ in done],
                   frames_counted=(ends[0], ends[-1], len(ends) - 1))
    out.memory_peak_bytes = memory_peak()
    served = [(r, rec.cams[c], j, raw.of(r.rects)) for r, c, j in done]
    del rec, svc, raw
    gc.collect()
    out.checks = check(served, cfg, arrays, traffic, seed, out.failed)
    return out
