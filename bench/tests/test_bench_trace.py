"""The reduction of a profiler trace to busy, idle and kernel times, on a
small recorded trace: 18 consecutive device ops of a TPU v5e running the
dense batch program (one fused-head kernel call among them, 100 us after
the window opens), and the benchmark's host spans around them."""

from __future__ import annotations

import json
import os

from bench import counts, trace
from bench.cascade import cascade_arrays, load_config

ROWS = os.path.join(os.path.dirname(__file__), "data", "trace_rows.json")
WINDOW_NS = 1_331_221_304


def rows() -> list[dict]:
    with open(ROWS) as f:
        return json.load(f)


def test_busy_and_idle_time():
    busy_s, merged = trace.busy(rows(), 0, WINDOW_NS)
    assert list(merged) == ["/device:TPU:0"]
    assert round(busy_s * 1e9) == 1_330_721_285
    assert WINDOW_NS - round(busy_s * 1e9) == 500_019


def test_kernel_time_and_its_shapes():
    r = rows()
    arrays = cascade_arrays(load_config("ff25-trained-vga"))
    hits = [x for x in r if counts.fused_head_call(x["name"], arrays)]
    assert [x["dur_ns"] for x in hits] == [1_330_718_051]
    calls = [counts.fused_head_call(x["name"], arrays) for x in hits]
    # (8, 25, 464, 640) stage sums: 8 * 464 * 640 origins x 47,485 ops;
    # reads the (8, 496, 768) table, the (8, 464, 640) 1/sigma grid and
    # the weak-classifier tables, writes the sums
    per_window = int(counts.weak_ops(arrays["rect_w"]).sum())
    assert per_window == 47_485
    assert calls == [(8 * 464 * 640 * per_window,
                      4 * (8 * 25 * 464 * 640 + 34956 + 8739 + 3 * 2913 + 26
                           + 8 * 496 * 768 + 8 * 464 * 640))]


def test_longest_idle_gaps_are_named_by_host_spans():
    gaps = trace.idle_gaps(rows(), 0, WINDOW_NS, n=2)
    assert gaps == [["bench.detect_batch+bench.wait", 400_000e-9],
                    ["bench.batch_program+bench.wait", 100_000e-9]]


def test_top_ops_by_short_name():
    top = trace.top_ops(rows(), n=2)
    assert top[0] == ["%vmap__.17 = f32[8,25,464,640] custom-call "
                      "tpu_custom_call", 1.330718051]
    assert top[1][0].startswith("%pad_maximum_fusion.1 = f32[8,464,640]")


def ctx_of_fixture() -> dict:
    """What a photo run hands its readers, around the recorded trace: one
    counted batch of 8 photos spanning the whole window, one photo of
    10 G useful operations completed inside it."""
    from bench.peaks import peak

    r = rows()
    return {"trace_rows": r, "t0_ns": 0, "t1_ns": WINDOW_NS, "t0_host": 100.0,
            "window_s": WINDOW_NS / 1e9,
            "busy_s": trace.busy(r, 0, WINDOW_NS)[0],
            "counted": [(100.0, 100.0 + WINDOW_NS / 1e9, 8)],
            "served": [(100.5, 10**10), (99.0, 10**10)],
            "arrays": cascade_arrays(load_config("ff25-trained-vga")),
            "peak": peak("TPU v5 lite")}


def test_photo_metric_readers_on_the_recorded_trace():
    from bench.harness import read_metric

    ctx = ctx_of_fixture()
    ms = read_metric("fused_head.ms_per_image.photos", ctx)
    assert ms == 1330.718051 / 8
    share = read_metric("fused_head_roofline.photos", ctx)
    assert abs(share - 100 * (8 * 464 * 640 * 47_485 / 1.97e14)
               / 1.330718051) < 1e-9
    assert 0 < share < 100
    idle = read_metric("device.idle_share.photos", ctx)
    assert abs(idle - 100 * 500_019 / WINDOW_NS) < 1e-9
    mfu = read_metric("mfu.photos", ctx)
    assert abs(mfu - 100 * 10**10 / (WINDOW_NS / 1e9) / 1.97e14) < 1e-12


def test_readers_find_nothing_without_a_trace():
    from bench.harness import read_metric

    for name in ("fused_head.ms_per_image.photos", "fused_head_roofline.photos",
                 "mfu.photos", "device.idle_share.photos",
                 "device.idle_share.cctv", "stream.window_skip.cctv",
                 "stream.full_frames.cctv"):
        assert read_metric(name, {"trace_rows": None}) is None


def test_stream_metric_readers():
    from types import SimpleNamespace

    from bench.harness import read_metric

    stats = [SimpleNamespace(mode=m, windows_total=1000, windows_recomputed=r)
             for m, r in (("full", 1000), ("cached", 0), ("incremental", 200),
                          ("cached", 0))]
    ctx = {"frame_stats": stats}
    assert read_metric("stream.window_skip.cctv", ctx) == 100 * (1 - 1200 / 4000)
    assert read_metric("stream.full_frames.cctv", ctx) == 25.0
