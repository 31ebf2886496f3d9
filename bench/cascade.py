"""The cascade of a configuration, as plain arrays.

A configuration file (``bench/configs/<name>.json``) names the cascade's
shape (stage sizes, window) and the seed of its features, and states every
stage threshold.  The features are drawn the way the repository draws the
paper-shaped cascade (``repro.core.cascade.paper_shaped_cascade``): random
two- and three-rectangle Haar features inside the 24 x 24 window, stump
thresholds and votes.  The benchmark draws them itself, so the plain
reference and the program get the same weights from this one place.
"""

from __future__ import annotations

import json
import os

import numpy as np

WINDOW = 24
MAX_RECTS = 3
CONFIG_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "configs")


def load_config(name: str) -> dict:
    with open(os.path.join(CONFIG_DIR, f"{name}.json")) as f:
        return json.load(f)


def draw_features(seed: int, sizes: list[int]) -> dict[str, np.ndarray]:
    """Weak classifiers of a cascade with ``sizes`` per stage: rectangles
    (x, y, w, h) and weights, stump thresholds, left and right votes."""
    rng = np.random.default_rng(seed)
    n = int(np.sum(sizes))
    x = rng.integers(0, WINDOW - 6, size=n)
    y = rng.integers(0, WINDOW - 6, size=n)
    w = rng.integers(2, np.maximum(3, (WINDOW - x) // 2), size=n)
    h = rng.integers(2, np.maximum(3, WINDOW - y), size=n)
    three = rng.random(n) < 0.25
    horiz = rng.random(n) < 0.5
    rect_xywh = np.zeros((n, MAX_RECTS, 4), np.int32)
    rect_w = np.zeros((n, MAX_RECTS), np.float32)
    for i in range(n):
        k = 3 if three[i] else 2
        if horiz[i]:
            ww = max(min(w[i], (WINDOW - x[i]) // k), 1)
            for r in range(k):
                rect_xywh[i, r] = (x[i] + r * ww, y[i], ww, h[i])
        else:
            hh = max(min(h[i], (WINDOW - y[i]) // k), 1)
            for r in range(k):
                rect_xywh[i, r] = (x[i], y[i] + r * hh, w[i], hh)
        rect_w[i, :k] = (1.0, -1.0) if k == 2 else (1.0, -2.0, 1.0)
    return {
        "rect_xywh": rect_xywh,
        "rect_w": rect_w,
        "wc_threshold": rng.normal(0.0, 0.02, n).astype(np.float32),
        "left_val": rng.uniform(-1.0, 0.2, n).astype(np.float32),
        "right_val": rng.uniform(-0.2, 1.0, n).astype(np.float32),
        "stage_offsets": np.concatenate([[0], np.cumsum(sizes)]).astype(
            np.int32),
    }


def cascade_arrays(cfg: dict) -> dict[str, np.ndarray]:
    """Every array of the configuration's cascade, stage thresholds from
    the file."""
    arrays = draw_features(cfg["feature_seed"], cfg["stage_sizes"])
    arrays["stage_threshold"] = np.asarray(cfg["stage_threshold"], np.float32)
    if len(arrays["stage_threshold"]) != len(cfg["stage_sizes"]):
        raise ValueError("one stage threshold per stage")
    return arrays
