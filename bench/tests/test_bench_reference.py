"""The plain reference against the program on tiny frames, and the
reference's grouping against the program's."""

from __future__ import annotations

import numpy as np
import pytest

from bench import reference, scenes
from bench.photos import same_rects
from bench.tests import tiny


def program(cfg: dict, arrays: dict):
    from repro.core import Detector, EngineConfig
    from repro.core.cascade import make_cascade

    cascade = make_cascade(*(arrays[k] for k in (
        "rect_xywh", "rect_w", "wc_threshold", "left_val", "right_val",
        "stage_offsets", "stage_threshold")))
    return Detector(cascade, EngineConfig(
        step=cfg["step"], scale_factor=cfg["scale_factor"],
        min_neighbors=cfg["min_neighbors"], pad_multiple=cfg["pad_multiple"]))


@pytest.mark.parametrize("hw", [(64, 96), (50, 70)])
def test_reference_agrees_with_detect_on_a_tiny_frame(hw):
    """Survivors, per-stage alive counts and grouped rects; (50, 70) is
    served padded to (64, 96), so windows that reach padding are masked."""
    cfg = tiny.config()
    arrays = tiny.arrays(cfg)
    det = program(cfg, arrays)
    img = scenes.photo_pool(3, 1, *hw, (1, 4))[0]
    ref = reference.evaluate(img, arrays, cfg)
    res = det.batch_result([img.astype(np.float32)])
    val = np.asarray(res.valid)
    got = {tuple(r) for r in np.stack([np.asarray(a)[val] for a in (
        res.lvl, res.ys, res.xs)], axis=1).tolist()}
    assert len(ref.survivors) > 20
    assert got == {tuple(r) for r in ref.survivors.tolist()}
    assert np.asarray(res.alive_counts)[:, 0].tolist() == ref.counts.tolist()
    levels = reference.pyramid(*reference.bucket(*hw, cfg["pad_multiple"]),
                               cfg["scale_factor"])
    want = reference.group(reference.rects_of(ref.survivors, levels),
                           cfg["min_neighbors"])
    assert len(want) > 0
    assert same_rects(det.detect(img.astype(np.float32)), want)


@pytest.mark.parametrize("min_neighbors", [0, 3])
def test_grouping_matches_the_programs(min_neighbors):
    from repro.core.nms import group_rectangles

    rng = np.random.default_rng(3)
    xy = rng.integers(0, 120, (600, 2))
    side = rng.integers(24, 40, (600, 1))
    rects = np.concatenate([xy, side, side], axis=1)
    got = reference.group(rects, min_neighbors)
    want = group_rectangles(rects, min_neighbors)
    assert len(got) > 5
    assert np.array_equal(got, want)      # same clusters, same order


def test_bfloat16_reference_departs_from_float32():
    cfg = tiny.config()
    arrays = tiny.arrays(cfg)
    img = scenes.photo_pool(12, 1, 64, 96, (1, 3))[0]
    f32 = reference.evaluate(img, arrays, cfg)
    bf16 = reference.evaluate(img, arrays, cfg, "bfloat16")
    assert {tuple(r) for r in f32.survivors.tolist()} != {
        tuple(r) for r in bf16.survivors.tolist()}
