#!/usr/bin/env python
"""CI crossover smoke: the Pallas packed-tail backend must be bit-identical
to the gather oracle everywhere a tail runs.

Covers, on the pretrained cascade and the synthetic test corpus:

1. ``packed_tail.stage_sums`` backend sweep — every cascade stage, at
   deliberately non-rung-aligned survivor counts (odd sizes that exercise
   the kernel's lane-block padding), on a packed list spanning two images
   and two pyramid levels;
2. ``Detector.detect_batch(strategy="packed")`` with the tail forced to
   each backend, on a mixed ``valid_hw`` pad bucket (different true shapes
   inside one compiled program).

Exit code 0 = all bit-identical.  Run by ``scripts/ci.sh``.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import Detector, EngineConfig  # noqa: E402
from repro.core.cascade import WINDOW  # noqa: E402
from repro.core.integral import integral_images, window_inv_sigma  # noqa: E402
from repro.core.training.data import render_scene  # noqa: E402
from repro.configs.viola_jones import pretrained  # noqa: E402
from repro.kernels import packed_tail  # noqa: E402


def check_stage_sums(casc) -> None:
    """Backend sweep on a two-image, two-level packed list, odd sizes."""
    rng = np.random.default_rng(0)
    levels = [(80, 96), (56, 64)]                 # (h, w) per pyramid level
    sats, pairs, bases, strides = [], [], [], []
    base = 0
    for h, w in levels:
        imgs = np.stack([render_scene(rng, h, w, n_faces=1)[0]
                         for _ in range(2)])
        ii = np.stack([np.asarray(integral_images(jnp.asarray(im))[0])
                       for im in imgs])
        pr = np.stack([np.asarray(integral_images(jnp.asarray(im))[1])
                       for im in imgs])
        sats.append(ii.reshape(2, -1))
        pairs.append((pr, h, w))
        bases.append(base)
        strides.append(w + 1)
        base += (h + 1) * (w + 1)
    ii_flat = jnp.asarray(np.concatenate(sats, axis=1))

    for cap in (37, 317, 1111):                   # non-rung-aligned counts
        lv = rng.integers(0, len(levels), cap)
        img = rng.integers(0, 2, cap).astype(np.int32)
        ys = np.asarray([rng.integers(0, levels[v][0] - WINDOW + 1)
                         for v in lv], np.int32)
        xs = np.asarray([rng.integers(0, levels[v][1] - WINDOW + 1)
                         for v in lv], np.int32)
        b = np.asarray([bases[v] for v in lv], np.int32)
        st = np.asarray([strides[v] for v in lv], np.int32)
        inv = np.empty(cap, np.float32)
        for i in range(cap):
            pr, _h, _w = pairs[lv[i]]
            inv[i] = np.asarray(window_inv_sigma(
                jnp.asarray(pr[img[i]]), jnp.asarray(ys[i]),
                jnp.asarray(xs[i]), WINDOW))
        args = (ii_flat, jnp.asarray(img), jnp.asarray(b), jnp.asarray(st),
                jnp.asarray(ys), jnp.asarray(xs), jnp.asarray(inv))
        want = np.asarray(packed_tail.stage_sums(
            casc, casc, 0, casc.n_stages, *args, backend="gather"))
        for bk in ("bulk", "pallas"):
            got = np.asarray(packed_tail.stage_sums(
                casc, casc, 0, casc.n_stages, *args, backend=bk))
            assert np.array_equal(got, want), (
                f"stage_sums backend={bk} diverged at cap={cap}: "
                f"max|d|={np.abs(got - want).max()}")
        print(f"  stage_sums cap={cap}: all stages bit-identical "
              f"(gather == bulk == pallas)")


def check_detect_batch(casc) -> None:
    """Forced-backend detect_batch on a mixed-shape pad bucket."""
    rng = np.random.default_rng(1)
    shapes = [(96, 96), (80, 90), (88, 70)]       # one (96, 96) bucket
    imgs = [render_scene(rng, h, w, n_faces=1)[0] for h, w in shapes]
    kw = dict(mode="wave", step=1, scale_factor=1.2, min_neighbors=2,
              dense_segments=(1,), pad_multiple=96)
    want = Detector(casc, EngineConfig(tail_backend="gather", **kw)
                    ).detect_batch(imgs, strategy="packed")
    for bk in ("bulk", "pallas"):
        got = Detector(casc, EngineConfig(tail_backend=bk, **kw)
                       ).detect_batch(imgs, strategy="packed")
        for i, (g, w_) in enumerate(zip(got, want)):
            assert np.array_equal(g, w_), (
                f"detect_batch backend={bk} diverged on image {i}")
    print(f"  detect_batch: mixed valid_hw bucket bit-identical across "
          f"backends ({len(imgs)} images)")


def main() -> None:
    casc, _ = pretrained()
    print("crossover smoke: pallas packed tail vs gather oracle")
    check_stage_sums(casc)
    check_detect_batch(casc)
    print("crossover smoke OK")


if __name__ == "__main__":
    main()
