"""Seeded scenes: one-shot photos and parked-camera streams.

The benchmark's own copy of the scene model of ``repro.core.training.data``
(``make_face``/``make_background``/``render_scene``) and of the
``intermittent_cctv`` scenario of ``repro.stream.synthetic``: a textured
background with planted synthetic faces, and a parked camera watching it while
a small dark object moves now and then.  Frames are quantised to uint8, as a
camera or a JPEG decoder hands them over.
"""

from __future__ import annotations

import numpy as np


def _ellipse(h: int, w: int, cy: float, cx: float, ry: float,
             rx: float) -> np.ndarray:
    yy, xx = np.mgrid[0:h, 0:w]
    return ((yy - cy) / ry) ** 2 + ((xx - cx) / rx) ** 2 <= 1.0


def make_face(rng: np.random.Generator, size: int) -> np.ndarray:
    """One synthetic face patch (size x size), float32 in [0, 255]."""
    s = size / 24.0
    brightness = rng.uniform(100, 210)
    cx = (12 + rng.uniform(-1.8, 1.8)) * s
    cy = (12.5 + rng.uniform(-1.8, 1.8)) * s
    skin = brightness + rng.normal(0, 7, (size, size))
    img = np.full((size, size), brightness * rng.uniform(0.3, 0.9))
    img += rng.normal(0, 9, (size, size))
    head = _ellipse(size, size, cy, cx, rng.uniform(9.5, 11.8) * s,
                    rng.uniform(7, 9.8) * s)
    img[head] = skin[head]
    eye_y = cy - rng.uniform(2.6, 4.4) * s
    eye_dx = rng.uniform(3.2, 5.0) * s
    eye_r = rng.uniform(1.1, 2.0) * s
    dark = brightness * rng.uniform(0.25, 0.55)
    for side in (-1, 1):
        eye = _ellipse(size, size, eye_y + rng.uniform(-0.5, 0.5) * s,
                       cx + side * eye_dx, eye_r * 0.75, eye_r)
        img[eye] = dark + rng.normal(0, 5, img[eye].shape)
    if rng.random() < 0.8:
        brow = _ellipse(size, size, eye_y - rng.uniform(1.6, 2.8) * s, cx,
                        0.9 * s, rng.uniform(5, 7) * s)
        img[brow] = np.minimum(img[brow], brightness * rng.uniform(0.4, 0.75))
    nose = _ellipse(size, size, cy + rng.uniform(0, 1.5) * s, cx,
                    rng.uniform(2.4, 3.8) * s, rng.uniform(0.8, 1.4) * s)
    img[nose] = np.maximum(img[nose], brightness * rng.uniform(0.98, 1.18))
    mouth = _ellipse(size, size, cy + rng.uniform(4.8, 6.8) * s, cx,
                     rng.uniform(0.7, 1.5) * s, rng.uniform(2.6, 4.8) * s)
    img[mouth] = brightness * rng.uniform(0.28, 0.6)
    yy, xx = np.mgrid[0:size, 0:size]
    gy, gx = rng.normal(0, 18, 2)
    img = img + gy * (yy / size - 0.5) + gx * (xx / size - 0.5)
    img = (img - img.mean()) * rng.uniform(0.7, 1.25) + img.mean()
    if rng.random() < 0.25:
        ob = int(rng.integers(2, max(3, int(5 * s))))
        tone = brightness * rng.uniform(0.2, 0.9)
        if rng.random() < 0.5:
            img[:ob] = tone
        else:
            img[:, :ob] = tone
    img += rng.normal(0, 4, (size, size))
    return np.clip(img, 0, 255).astype(np.float32)


def make_background(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    """Textured background: a gradient, blobs, rectangles and stripes."""
    img = np.full((h, w), rng.uniform(40, 215), np.float32)
    gy, gx = rng.normal(0, 30, 2)
    yy, xx = np.mgrid[0:h, 0:w]
    img += gy * (yy / max(h, 1) - 0.5) + gx * (xx / max(w, 1) - 0.5)
    for _ in range(rng.integers(4, 14)):
        kind = rng.integers(0, 3)
        amp = rng.uniform(-60, 60)
        if kind == 0:
            y0, x0 = rng.integers(0, h), rng.integers(0, w)
            hh = int(rng.integers(2, max(h // 2, 3)))
            ww = int(rng.integers(2, max(w // 2, 3)))
            img[y0:y0 + hh, x0:x0 + ww] += amp
        elif kind == 1:
            cy, cx = rng.uniform(0, h), rng.uniform(0, w)
            ry, rx = rng.uniform(2, h / 3 + 3), rng.uniform(2, w / 3 + 3)
            img[_ellipse(h, w, cy, cx, ry, rx)] += amp
        else:
            period = rng.integers(3, 17)
            phase = rng.integers(0, period)
            if rng.random() < 0.5:
                img[:, (xx[0] + phase) % period < period // 2] += amp
            else:
                img[(yy[:, 0] + phase) % period < period // 2] += amp
    img += rng.normal(0, 5, (h, w))
    return np.clip(img, 0, 255).astype(np.float32)


def render_scene(rng: np.random.Generator, h: int, w: int, n_faces: int,
                 face_sizes: tuple[int, int] = (24, 72)) -> np.ndarray:
    """A (h, w) uint8 scene with up to ``n_faces`` non-overlapping faces."""
    img = make_background(rng, h, w)
    boxes: list[tuple[int, int, int]] = []
    tries = 0
    while len(boxes) < n_faces and tries < 200:
        tries += 1
        fs = int(rng.integers(face_sizes[0], face_sizes[1] + 1))
        if fs > min(h, w):
            continue
        y0 = int(rng.integers(0, h - fs + 1))
        x0 = int(rng.integers(0, w - fs + 1))
        if any(x0 < bx + bs and bx < x0 + fs and y0 < by + bs and by < y0 + fs
               for bx, by, bs in boxes):
            continue
        img[y0:y0 + fs, x0:x0 + fs] = make_face(rng, fs)
        boxes.append((x0, y0, fs))
    return np.rint(img).astype(np.uint8)


def photo_pool(seed: int, n: int, h: int, w: int,
               faces: tuple[int, int]) -> list[np.ndarray]:
    """``n`` uint8 photos from one seed, each with ``faces[0]..faces[1]``
    planted faces."""
    rng = np.random.default_rng(seed)
    return [render_scene(rng, h, w, int(rng.integers(faces[0], faces[1] + 1)))
            for _ in range(n)]


class ParkedCamera:
    """A parked camera: a fixed seeded scene in which an ``obj`` x ``obj``
    dark object near the bottom edge moves ``move_px`` pixels every
    ``move_every`` frames and stands still in between, so most frames are
    bit-identical to the one before."""

    def __init__(self, seed: int, h: int, w: int, n_faces: int, obj: int,
                 move_px: int, move_every: int):
        rng = np.random.default_rng(seed)
        self.scene = render_scene(rng, h, w, n_faces)
        self.tone = np.uint8(rng.uniform(10, 60))
        self.x0 = int(rng.integers(0, w - obj))
        self.y0 = h - obj - 2
        self.obj, self.move_px, self.move_every = obj, move_px, move_every

    def frame(self, t: int) -> np.ndarray:
        """Frame ``t`` (uint8)."""
        w, obj = self.scene.shape[1], self.obj
        x = (self.x0 + (t // self.move_every) * self.move_px) % (w - obj)
        f = self.scene.copy()
        f[self.y0:self.y0 + obj, x:x + obj] = self.tone
        return f
