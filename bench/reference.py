"""Plain reference of the served cascade: what a frame's detections should be.

A straightforward implementation of Viola-Jones detection with the semantics
the program states (``repro.core``): a nearest-neighbour image pyramid at the
configured scale factor, summed-area tables, window normalisation by the
window's standard deviation, weak stumps, stage sums against stage
thresholds, and OpenCV ``groupRectangles`` grouping.  It shares no code with
the program and takes nothing the program made.

The summed-area tables are integer tables (int32; a wrapped difference of a
rectangle is exact at any image size, since every 24 x 24 sum fits), so every
rectangle sum and every Haar feature is exact; the normalisation, the votes
and the stage sums are float32, the precision the configuration states.
``precision="bfloat16"`` computes the same thing one precision lower (tables,
sums and votes in bfloat16): the control that the comparison must reject.

The cascade is evaluated densely, every stage on every window of every
level, on whatever device JAX runs on, one level at a time.
"""

from __future__ import annotations

import math
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

WINDOW = 24
CENTRE = 128


def pyramid(h: int, w: int, scale_factor: float) -> list[tuple[int, int, float]]:
    """(height, width, scale) of every level: the image shrunk by
    ``scale_factor`` per level while a whole window still fits."""
    levels, s = [], 1.0
    while True:
        lh, lw = int(math.floor(h / s)), int(math.floor(w / s))
        if lh < WINDOW or lw < WINDOW:
            return levels
        levels.append((lh, lw, s))
        s *= scale_factor


def bucket(h: int, w: int, pad_multiple: int) -> tuple[int, int]:
    """The padded shape an (h, w) image is served at."""
    if pad_multiple <= 0:
        return h, w
    up = lambda n: max(-(-n // pad_multiple) * pad_multiple, WINDOW)
    return up(h), up(w)


def _sat(x: jax.Array) -> jax.Array:
    """Zero-padded summed-area table, (h + 1, w + 1)."""
    return jnp.pad(jnp.cumsum(jnp.cumsum(x, axis=0), axis=1), ((1, 0), (1, 0)))


def _grid(t: jax.Array, ny: int, nx: int, y, x, h, w) -> jax.Array:
    """Sum of rectangle (x, y, w, h) of every window of the (ny, nx) grid."""
    c = lambda cy, cx: jax.lax.dynamic_slice(t, (cy, cx), (ny, nx))
    return c(y + h, x + w) - c(y, x + w) - c(y + h, x) + c(y, x)


def _stage_totals(img: jax.Array, rects: jax.Array, weights: jax.Array,
                  theta: jax.Array, left: jax.Array, right: jax.Array,
                  ny: int, nx: int, precision: str):
    """``total(k0, k1)``: the (ny, nx) sums of the votes of weak classifiers
    ``k0 .. k1 - 1`` over every window of one pyramid level, and the float
    type they are computed in."""
    ft = jnp.float32 if precision == "float32" else jnp.bfloat16
    if precision == "float32":
        x = img.astype(jnp.int32)
        ii, ic, i2 = _sat(x), _sat(x - CENTRE), _sat((x - CENTRE) ** 2)
    else:
        x = img.astype(ft)
        ii = _sat(x).astype(ft)
        ic = _sat(x - CENTRE).astype(ft)
        i2 = _sat((x - CENTRE) ** 2).astype(ft)
    area = WINDOW * WINDOW
    s1 = _grid(ic, ny, nx, 0, 0, WINDOW, WINDOW).astype(ft)
    s2 = _grid(i2, ny, nx, 0, 0, WINDOW, WINDOW).astype(ft)
    var = s2 / area - (s1 / area) ** 2
    inv_sigma = 1 / jnp.sqrt(jnp.maximum(var, 1))
    weights = weights.astype(ft)

    def weak(k, acc):
        feat = jnp.zeros((ny, nx), ft)
        for r in range(rects.shape[1]):
            rx, ry, rw, rh = (rects[k, r, i] for i in range(4))
            feat = feat + weights[k, r] * _grid(ii, ny, nx, ry, rx, rh,
                                                rw).astype(ft)
        f = feat * inv_sigma / area
        return acc + jnp.where(f < theta[k].astype(ft), left[k].astype(ft),
                               right[k].astype(ft))

    def total(k0, k1):
        return jax.lax.fori_loop(k0, k1, weak, jnp.zeros((ny, nx), ft))

    return total, ft


@partial(jax.jit, static_argnames=("ny", "nx", "precision"))
def _level(img: jax.Array, valid: jax.Array, rects: jax.Array,
           weights: jax.Array, theta: jax.Array, left: jax.Array,
           right: jax.Array, offsets: jax.Array, stage_thr: jax.Array, *,
           ny: int, nx: int, precision: str):
    """The final alive mask (ny, nx) and per-stage alive counts
    (n_stages,) of one pyramid level; ``valid`` masks windows that reach
    padding."""
    total, ft = _stage_totals(img, rects, weights, theta, left, right, ny,
                              nx, precision)

    def stage(s, carry):
        alive, counts = carry
        passed = total(offsets[s], offsets[s + 1]) >= stage_thr[s].astype(ft)
        alive = alive & passed
        return alive, counts.at[s].set(alive.sum(dtype=jnp.int32))

    n_stages = stage_thr.shape[0]
    return jax.lax.fori_loop(0, n_stages, stage,
                             (valid, jnp.zeros((n_stages,), jnp.int32)))


@partial(jax.jit, static_argnames=("ny", "nx"))
def level_stage_sums(img: jax.Array, rects: jax.Array, weights: jax.Array,
                     theta: jax.Array, left: jax.Array, right: jax.Array,
                     offsets: jax.Array, *, ny: int, nx: int) -> jax.Array:
    """(n_stages, ny, nx) float32 stage sums of every window of one level."""
    total, _ = _stage_totals(img, rects, weights, theta, left, right, ny, nx,
                             "float32")
    n_stages = offsets.shape[0] - 1
    return jax.lax.fori_loop(
        0, n_stages, lambda s, out: out.at[s].set(
            total(offsets[s], offsets[s + 1])),
        jnp.zeros((n_stages, ny, nx), jnp.float32))


def levels_of(image: np.ndarray, cfg: dict):
    """Every pyramid level of ``image`` as it is served: (level index, level
    pixels (int32), (ny, nx), mask of windows that sample only real
    pixels)."""
    h, w = image.shape
    hp, wp = bucket(h, w, cfg["pad_multiple"])
    padded = np.zeros((hp, wp), np.int32)
    padded[:h, :w] = image
    for li, (lh, lw, _s) in enumerate(pyramid(hp, wp, cfg["scale_factor"])):
        ys = (np.arange(lh) * hp) // lh
        xs = (np.arange(lw) * wp) // lw
        ny, nx = lh - WINDOW + 1, lw - WINDOW + 1
        y_lim = (h * lh - 1) // hp - (WINDOW - 1)
        x_lim = (w * lw - 1) // wp - (WINDOW - 1)
        valid = ((np.arange(ny) <= y_lim)[:, None]
                 & (np.arange(nx) <= x_lim)[None, :])
        yield li, padded[ys[:, None], xs[None, :]], (ny, nx), valid


class Frame:
    """What the reference finds in one frame: per-stage alive counts
    (windows left after each stage, summed over levels) and the surviving
    windows as (level, y, x) rows in level, row, column order."""

    def __init__(self, counts: np.ndarray, survivors: np.ndarray):
        self.counts = counts
        self.survivors = survivors


def evaluate(image: np.ndarray, cascade: dict, cfg: dict,
             precision: str = "float32") -> Frame:
    """Run the cascade over every window of ``image`` (uint8, (h, w))."""
    args = [jnp.asarray(cascade[k]) for k in (
        "rect_xywh", "rect_w", "wc_threshold", "left_val", "right_val",
        "stage_offsets", "stage_threshold")]
    counts = np.zeros(len(cascade["stage_threshold"]), np.int64)
    found = []
    for li, level, (ny, nx), valid in levels_of(image, cfg):
        alive, c = _level(jnp.asarray(level), jnp.asarray(valid), *args,
                          ny=ny, nx=nx, precision=precision)
        counts += np.asarray(c)
        yy, xx = np.nonzero(np.asarray(alive))
        found.append(np.stack([np.full_like(yy, li), yy, xx], axis=1))
    return Frame(counts, np.concatenate(found).astype(np.int64))


def rects_of(survivors: np.ndarray, levels: list[tuple[int, int, float]]
             ) -> np.ndarray:
    """(level, y, x) windows -> [x, y, w, h] rectangles in image pixels,
    rounded half to even."""
    scale = np.asarray([s for _h, _w, s in levels], np.float64)[
        survivors[:, 0]]
    side = np.rint(WINDOW * scale)
    return np.stack([np.rint(survivors[:, 2] * scale),
                     np.rint(survivors[:, 1] * scale), side, side],
                    axis=1).astype(np.int64)


def group(rects: np.ndarray, min_neighbors: int, eps: float = 0.2
          ) -> np.ndarray:
    """OpenCV ``groupRectangles``: join every two rects whose four edges lie
    within ``eps * (min width + min height) / 2`` of each other, take the
    connected clusters, keep those of more than ``min_neighbors`` rects,
    and return each kept cluster's mean rect (rounded half to even), in the
    order of each cluster's first rect."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components
    from scipy.spatial import cKDTree

    r = np.asarray(rects, np.float64).reshape(-1, 4)
    n = len(r)
    if n == 0:
        return np.zeros((0, 4), np.int64)
    edges = np.stack([r[:, 0], r[:, 1], r[:, 0] + r[:, 2], r[:, 1] + r[:, 3]],
                     axis=1)
    # two rects can join only if every edge lies within eps * (w + h) / 2
    # of either one's: look those up, then test the pair's own bound
    reach = eps * (r[:, 2] + r[:, 3]) / 2
    tree = cKDTree(edges)
    pi, pj = [], []
    for far in np.unique(reach):
        rows = np.nonzero(reach == far)[0]
        near = cKDTree(edges[rows]).sparse_distance_matrix(
            tree, far, p=np.inf, output_type="ndarray")
        i, j = rows[near["i"]], near["j"]
        d = eps * (np.minimum(r[i, 2], r[j, 2])
                   + np.minimum(r[i, 3], r[j, 3])) / 2
        sim = (np.abs(edges[i] - edges[j]) <= d[:, None]).all(axis=1)
        pi.append(i[sim])
        pj.append(j[sim])
    pi, pj = np.concatenate(pi), np.concatenate(pj)
    graph = coo_matrix((np.ones(len(pi)), (pi, pj)), shape=(n, n))
    _, label = connected_components(graph, directed=False)
    first = np.full(label.max() + 1, n)
    np.minimum.at(first, label, np.arange(n))
    count = np.bincount(label)
    sums = np.zeros((len(count), 4))
    np.add.at(sums, label, r)
    keep = np.nonzero(count > min_neighbors)[0]
    keep = keep[np.argsort(first[keep])]
    return np.rint(sums[keep] / count[keep, None]).astype(np.int64)
