"""Traffic generators, operation and byte counts, the peak table and the
benchmark's own data files."""

from __future__ import annotations

import os

import numpy as np
import pytest

from bench import counts, harness, scenes
from bench.cascade import cascade_arrays, load_config
from bench.peaks import peak
from bench.run import cell_metrics


def test_photo_pool_is_deterministic_per_seed():
    a = scenes.photo_pool(2**33 + 1, 3, 48, 64, (1, 4))
    b = scenes.photo_pool(2**33 + 1, 3, 48, 64, (1, 4))
    c = scenes.photo_pool(2**33 + 2, 3, 48, 64, (1, 4))
    assert all(x.dtype == np.uint8 and x.shape == (48, 64) for x in a)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))


def test_parked_camera_is_deterministic_and_mostly_still():
    cam = scenes.ParkedCamera(7, 96, 128, 1, obj=16, move_px=8, move_every=4)
    again = scenes.ParkedCamera(7, 96, 128, 1, obj=16, move_px=8,
                                move_every=4)
    frames = [cam.frame(t) for t in range(9)]
    assert all(np.array_equal(f, again.frame(t)) for t, f in enumerate(frames))
    same = [np.array_equal(frames[t], frames[t + 1]) for t in range(8)]
    assert same == [True, True, True, False, True, True, True, False]


def test_feature_draw_is_deterministic():
    cfg = load_config("ff25-deep-vga")
    a, b = cascade_arrays(cfg), cascade_arrays(cfg)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert a["rect_xywh"].shape == (2913, 3, 4)


def tiny_cascade() -> dict:
    """Two stages: a two-rectangle feature, then a three-rectangle one."""
    return {
        "rect_w": np.asarray([[1, -1, 0], [1, -2, 1]], np.float32),
        "stage_offsets": np.asarray([0, 1, 2], np.int32),
        "stage_threshold": np.zeros(2, np.float32),
        "rect_xywh": np.zeros((2, 3, 4), np.int32),
        "wc_threshold": np.zeros(2, np.float32),
        "left_val": np.zeros(2, np.float32),
        "right_val": np.zeros(2, np.float32),
    }


def test_counts_match_hand_counts():
    c = tiny_cascade()
    # per rect: 3 corner adds, 1 weight multiply, 1 accumulate; per weak:
    # 2 multiplies, 1 compare, 1 select, 1 accumulate
    assert counts.weak_ops(c["rect_w"]).tolist() == [15, 20]
    assert counts.stage_ops(c["rect_w"], c["stage_offsets"]).tolist() == [
        15, 20]
    # a call over a batch of 2 and both stages of a 7 x 9 grid: reads an
    # (2, 16, 128) table and a (2, 8, 128) 1/sigma grid, writes (2, 2, 7, 9)
    hlo = ('%k = f32[2,2,7,9]{3,2,1,0:T(8,128)} custom-call(f32[2,16,128]'
           '{2,1,0:T(8,128)} %a, f32[2,8,128]{2,1,0:T(8,128)} %b), '
           'custom_call_target="tpu_custom_call"')
    assert counts.fused_head_call(hlo, c) == (
        2 * 7 * 9 * 35, 4 * (2 * 2 * 7 * 9 + 2 * 16 * 128 + 2 * 8 * 128))
    assert counts.fused_head_call(hlo.replace("tpu_custom_call", "x"), c) \
        is None
    # 53 windows enter stage 0, 20 of them stage 1
    entering = counts.entering([20, 3], 53)
    assert entering.tolist() == [53, 20]
    assert counts.useful_ops(entering, c) == 53 * 15 + 20 * 20


def test_peak_table_rejects_unknown_device_kind():
    assert peak("TPU v5 lite")["flops_per_s"] == 1.97e14
    with pytest.raises(KeyError, match="no published peaks"):
        peak("TPU v99")


def test_benchmark_files_are_complete():
    """Every cell finds its configuration, traffic and metric readers by
    name, and reports setup_s, another end-to-end metric and a per-layer
    metric."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    configs = {c["name"]: c for c in bench["configs"]}
    for cfg in bench["configs"]:
        assert os.path.isfile(os.path.join(harness.ROOT, cfg["file"]))
        assert load_config(cfg["name"])["name"] == cfg["name"]
    for cell in bench["workloads"]:
        assert cell["config"] in configs
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
        traffic = harness.load_json(harness.BENCH_DIR, "traffic",
                                    f"{cell['traffic']}.json")
        assert os.path.isfile(os.path.join(harness.BENCH_DIR,
                                           f"{traffic['kind']}.py"))
        e2e = [m["name"] for m in cell_metrics(bench, cell["name"],
                                               "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = cell_metrics(bench, cell["name"], "per_layer")
        assert layer
        for m in layer:
            assert os.path.isfile(os.path.join(
                harness.BENCH_DIR, "metrics", f"{m['name']}.py"))


def test_command_refuses_to_run_without_a_tpu():
    """On the CPU the command exits non-zero and prints no result."""
    import subprocess
    import sys

    cell = harness.load_json(harness.ROOT, "BENCHMARK.json")["workloads"][0]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", cell["name"],
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert "needs 1 TPU chip" in done.stderr
    assert '"correct"' not in done.stdout
