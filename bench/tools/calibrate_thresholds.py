"""Stage thresholds of a cascade that rejects like a trained one.

    JAX_PLATFORMS=cpu python3 -m bench.tools.calibrate_thresholds \
        --base ff25-deep-vga --out ff25-trained-vga

OpenCV's ``traincascade`` trains each stage to pass about half of the
non-face windows that reach it (``maxFalseAlarmRate`` 0.5).  This tool keeps
the features of the ``--base`` configuration and resets its stage thresholds
so that each stage passes half of the windows of a few seeded background
scenes that reach it.  Once fewer than ``--min-reach`` windows reach a stage,
the stage's threshold is the median of its sums over all sample windows.  Each
threshold lies halfway between two neighbouring sums, so no sample window
sits on it.  The plain reference computes the sums (exact integer
summed-area tables, float32 votes).

It writes the thresholds, the pass profile they reach on the sample and on
held-out scenes, and the mean number of weak classifiers a window needs with
early exit, into ``bench/configs/<out>.json``.
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np
import jax.numpy as jnp

from bench import reference, scenes
from bench.cascade import CONFIG_DIR, cascade_arrays, load_config

SAMPLE_SEED = 20240205      # background scenes the thresholds are set on
HELD_OUT_SEED = 20240206    # background scenes the profile is checked on


def stage_sums(images: list[np.ndarray], arrays: dict, cfg: dict
               ) -> np.ndarray:
    """(n_stages, n_windows) stage sums of every window the images serve."""
    args = [jnp.asarray(arrays[k]) for k in (
        "rect_xywh", "rect_w", "wc_threshold", "left_val", "right_val",
        "stage_offsets")]
    parts = []
    for img in images:
        for _li, level, (ny, nx), valid in reference.levels_of(img, cfg):
            sums = np.asarray(reference.level_stage_sums(
                jnp.asarray(level), *args, ny=ny, nx=nx))
            parts.append(sums[:, valid])
    return np.concatenate(parts, axis=1)


def between(values: np.ndarray) -> float:
    """A float32 threshold halfway between two neighbouring distinct values
    that passes (``>=``) the share of ``values`` nearest to one half."""
    v, n = np.unique(values, return_counts=True)
    if len(v) == 1:
        return float(np.float32(v[0] - 1))
    passed = 1 - np.cumsum(n)[:-1] / len(values)   # share >= v[i + 1]
    i = int(np.argmin(np.abs(passed - 0.5)))
    return float(np.float32((v[i] + v[i + 1]) / 2))


def calibrate(sums: np.ndarray, min_reach: int) -> list[float]:
    alive = np.ones(sums.shape[1], bool)
    thr = []
    for s in range(sums.shape[0]):
        reach = sums[s, alive] if alive.sum() >= min_reach else sums[s]
        thr.append(between(reach))
        alive &= sums[s] >= np.float32(thr[-1])
    return thr


def profile(sums: np.ndarray, thr: list[float], sizes: list[int]) -> dict:
    """Per-stage pass rates of the windows reaching each stage, the share
    of windows alive at the end, and the mean weak classifiers a window
    needs when it stops at its first failed stage."""
    n = sums.shape[1]
    alive = np.ones(n, bool)
    rates, entering = [], []
    for s, t in enumerate(thr):
        before = int(alive.sum())
        entering.append(before)
        alive &= sums[s] >= np.float32(t)
        rates.append(round(int(alive.sum()) / max(before, 1), 4))
    return {
        "windows": n,
        "pass_rate": rates,
        "alive_share_at_end": int(alive.sum()) / n,
        "mean_weak_per_window": round(
            float(np.dot(entering, sizes)) / n, 2),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--base", default="ff25-deep-vga")
    ap.add_argument("--out", default="ff25-trained-vga")
    ap.add_argument("--scenes", type=int, default=16)
    ap.add_argument("--min-reach", type=int, default=100)
    args = ap.parse_args()

    base = load_config(args.base)
    h, w = base["frame_hw"]
    sample = scenes.photo_pool(SAMPLE_SEED, args.scenes, h, w, (0, 0))
    held = scenes.photo_pool(HELD_OUT_SEED, 4, h, w, (0, 0))
    arrays = cascade_arrays(base)
    sums = stage_sums(sample, arrays, base)
    thr = calibrate(sums, args.min_reach)
    out_path = os.path.join(CONFIG_DIR, f"{args.out}.json")
    with open(out_path) as f:
        cfg = json.load(f)
    cfg["stage_threshold"] = thr
    cfg["calibration"] = {
        "tool": "bench/tools/calibrate_thresholds.py",
        "sample_scenes": [SAMPLE_SEED, args.scenes],
        "held_out_scenes": [HELD_OUT_SEED, 4],
        "min_reach": args.min_reach,
        "sample": profile(sums, thr, base["stage_sizes"]),
        "held_out": profile(stage_sums(held, arrays, base), thr,
                            base["stage_sizes"]),
    }
    with open(out_path, "w") as f:
        json.dump(cfg, f, indent=1)
        f.write("\n")
    print(json.dumps(cfg["calibration"], indent=1))


if __name__ == "__main__":
    main()
