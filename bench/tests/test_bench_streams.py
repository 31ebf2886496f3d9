"""The parked-camera driver on the CPU at a tiny size: a sound run passes
its check; a stream whose cached decision outlives a changed frame, and an
answer altered where it is produced, come out not correct."""

from __future__ import annotations

import time

import pytest

from bench import harness, streams
from bench.tests import tiny


def run(seed: int = 2**33 + 21):
    cfg = tiny.config("ff25-trained-vga")
    cfg["frame_hw"] = [128, 192]
    traffic = harness.load_json(harness.BENCH_DIR, "traffic",
                                "cctv-8cam.json")
    traffic.update(cameras=3, object=24, move_px=12, move_every=2,
                   keyframe_interval=16, rate_fps=12.0, check_frames=24)
    return streams.run(cfg, tiny.arrays(cfg), traffic, seed, 2.0, None,
                       time.perf_counter())


def test_sound_stream_run_is_correct():
    out = run()
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted >= 20
    modes = {s.mode for s in out.ctx["frame_stats"]}
    assert {"cached", "incremental"} <= modes


def test_stream_whose_state_never_moves(monkeypatch):
    """Every frame after a session's first gets the first frame's answer,
    as if the stream step returned its state unchanged."""
    from repro.stream import video

    commit = video.VideoDetector.commit_token
    first: dict = {}

    def unchanged(self, tok):
        out = commit(self, tok)
        return first.setdefault(id(self), out)

    monkeypatch.setattr(video.VideoDetector, "commit_token", unchanged)
    out = run(seed=2**33 + 22)
    assert not out.correct
    assert out.checks["survivors_off"][0] > out.checks["survivors_off"][1]


@pytest.mark.parametrize("shift", [1])
def test_stream_answer_altered(monkeypatch, shift):
    from repro.core import nms

    group = nms.group_rectangles

    def altered(rects, *a, **k):
        out = group(rects, *a, **k)
        if len(out):
            out = out.copy()
            out[0, 1] += shift
        return out

    monkeypatch.setattr(nms, "group_rectangles", altered)
    out = run(seed=2**33 + 23)
    assert not out.correct
