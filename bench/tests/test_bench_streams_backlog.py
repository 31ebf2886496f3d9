"""The closed-loop camera driver on the CPU at a tiny size (a sound run is
correct; the set-up watchdog and the program probe fail with errors that
name the set-up), and the readers of the stream metrics on a hand-built
run: a counted span from host second 8.125 to 8.875 (trace time 125 to
875 ms) with 6 frames completed in it, and a ring of the program's stream
spans, some inside it and some outside."""

from __future__ import annotations

import collections
import sys
import time

import pytest

from bench import harness, stream_counts, streams_backlog
from bench.harness import read_metric
from bench.tests import tiny
from repro import obs

S = 1_000_000_000
MS = 1_000_000


def run(seed: int = 2**33 + 31):
    cfg = tiny.config("ff25-trained-vga-parked")
    cfg["frame_hw"] = [96, 128]
    traffic = harness.load_json(harness.BENCH_DIR, "traffic",
                                "cctv-8cam-backlog.json")
    traffic.update(cameras=2, object=20, move_px=12, move_every=2,
                   keyframe_interval=8, check_frames=6)
    return streams_backlog.run(cfg, tiny.arrays(cfg), traffic, seed, 1.5,
                               None, time.perf_counter())


def test_sound_backlog_run_is_correct():
    out = run()
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted >= 8
    assert out.e2e["images_per_s"] > 0
    first, last, n = out.ctx["frames_counted"]
    assert last > first and n == out.attempted - 1
    modes = {s.mode for s in out.ctx["frame_stats"]}
    assert "full" in modes and modes & {"cached", "incremental"}
    assert "program builds inside the window: 0" in out.notes


def test_watchdog_names_the_setup_without_waiting():
    fired = []
    dog = streams_backlog.Watchdog(time.perf_counter() - 5.0, 0.25,
                                   on_fire=fired.append).start()
    dog.timer.join(5.0)
    assert fired and "set-up did not finish within 0.25 s" in fired[0]


def test_watchdog_stays_quiet_once_setup_is_done():
    fired = []
    dog = streams_backlog.Watchdog(time.perf_counter(), 30.0,
                                   on_fire=fired.append).start()
    dog.done()
    dog.timer.join(5.0)
    assert not fired and not dog.timer.is_alive()


def test_program_whose_step_sends_keyframes_to_the_host_exits(monkeypatch,
                                                              capsys):
    from repro.stream import engine

    old = collections.namedtuple("StreamStepOut", [
        "mode", "tiles_changed", "n_rec", "levels_active", "retry",
        "n_surv", "slots"])
    monkeypatch.setattr(engine, "StreamStepOut", old)
    with pytest.raises(SystemExit):
        streams_backlog.needs_device_keyframes()
    assert "set-up cannot finish" in capsys.readouterr().err


def test_configuration_states_the_sessions_the_driver_opens():
    cfg = tiny.config("ff25-trained-vga-parked")
    assert cfg["stream"] == streams_backlog.SESSIONS
    cfg["stream"] = dict(cfg["stream"], threshold=2.0)
    with pytest.raises(SystemExit, match="states sessions"):
        streams_backlog.run(cfg, {}, {}, 1, 1.0, None, time.perf_counter())


# ------------------------------------------------------------- readers
ARRAYS = {"rect_w": [[1.0, -1.0, 0.0], [1.0, -2.0, 1.0]],
          "stage_offsets": [0, 1, 2], "stage_threshold": [0.0, 0.0]}
PEAK = {"flops_per_s": 1e6, "hbm_bytes_per_s": 1e9}
HEAD = ('%custom-call.3 = f32[2,17,105]{2,1,0} custom-call(s32[41,233]{1,0}'
        ' %a, f32[24,128]{1,0} %b), custom_call_target="tpu_custom_call"')
HEAD_BYTES = 4 * (2 * 17 * 105 + 41 * 233 + 24 * 128)


def op(name: str, start_ms: float, end_ms: float) -> dict:
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": name,
            "start_ns": int(start_ms * MS),
            "dur_ns": int((end_ms - start_ms) * MS)}


def ctx() -> dict:
    # host second 8.0 is trace time 0; the traced window is [0, 1000) ms
    return {"trace_rows": [op(HEAD, 125, 250), op("fusion", 250, 375),
                           op("fusion.1", 150, 200), op(HEAD, 500, 750),
                           op(HEAD, 900, 950)],
            "t0_ns": 0, "t1_ns": S, "t0_host": 8.0, "window_s": 1.0,
            "peak": PEAK, "arrays": ARRAYS,
            "frames_counted": (8.125, 8.875, 6)}


def ring() -> list:
    out = []

    def add(name, t0_s, dur_ns, parent=0, **attrs):
        t0 = int(t0_s * S)
        out.append(obs.Span(name, t0, t0 + dur_ns, 1, len(out) + 1, parent,
                            attrs))
        return len(out)

    head = {"head_work": 3, "head_dense": 12, "head_windows": (2048, 1024)}
    # two frames polled in the counted span, one before it, one in the
    # traced window after it
    for t in (8.25, 8.5, 7.0, 8.9375):
        add("stream.poll", t, MS, mode=1, n_tiles=4, n_rec=9,
            levels_active=2, **head)
        add("stream.fetch", t + 0.0078125, 2 * MS, bytes=8192)
        dec = add("stream.decode", t + 0.015625, 4 * MS)
        add("nms.group", t + 0.015625, 3 * MS, parent=dec, n=5)
    add("stream.step", 8.3, 5 * MS, sess=0, frame=3)
    return out


OPS = 2 * (2048 * 15 + 1024 * 20)        # two polls in the counted span
EXPECT = {
    # device busy in [125, 875) ms: [125, 375) and [500, 750), 6 frames;
    # the op nested in the first head call counts once
    "stream.step.ms_per_frame.cctv": 500 / 6,
    # the head calls in the span: 375 ms for OPS operations at 1e6/s
    "stream_head_roofline.cctv": 100 * max(
        OPS / 1e6, 2 * HEAD_BYTES / 1e9) / 0.375,
    "stream.head.work_share.cctv": 100 * 6 / 24,
    # per counted poll: 1 + 2 + (4 - 3) + 3 ms of self time
    "stream.host.ms_per_frame.cctv": 2 * 7 / 6,
    # three polls start in the traced window
    "mfu.cctv": 100 * 3 * (2048 * 15 + 1024 * 20) / 1.0 / 1e6,
}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_value_on_a_hand_built_run(monkeypatch, name):
    monkeypatch.setattr(obs, "spans", ring)
    assert read_metric(name, ctx()) == pytest.approx(EXPECT[name], rel=1e-9)


def test_head_ops_count_the_stages_the_tiles_ran():
    assert stream_counts.head_ops((2048, 1024), ARRAYS) == 2048 * 15 + \
        1024 * 20
    assert stream_counts.head_call_bytes(HEAD, 2) == HEAD_BYTES
    assert stream_counts.head_call_bytes(HEAD, 25) is None


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_a_program_without_stream_spans_reads_nothing(monkeypatch, name):
    # the parent's program: no stream spans, and no head calls of 3 dims
    monkeypatch.setattr(obs, "spans", lambda: [])
    c = ctx()
    if name == "stream.step.ms_per_frame.cctv":
        c["trace_rows"] = []
    assert read_metric(name, c) is None


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_without_the_program_module(monkeypatch, name):
    monkeypatch.setattr(obs, "spans", ring)
    monkeypatch.delattr(sys.modules["repro"], "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    c = ctx()
    if name == "stream.step.ms_per_frame.cctv":
        c["trace_rows"] = []
    assert read_metric(name, c) is None
