"""Detection grouping — the equivalent of OpenCV's ``groupRectangles``.

Raw cascade output fires on many neighbouring windows/scales around a true
face; detections are clustered by rectangle similarity (union-find over an
eps-overlap predicate) and clusters with fewer than ``min_neighbors + 1``
members are discarded (OpenCV keeps a cluster iff its size is strictly
greater than ``groupThreshold``).  Host-side numpy: runs on the (small) set of accepted windows
after the device pipeline.

The similarity predicate is evaluated only on candidate pairs from an
x-sorted band (never an (N, N) matrix), and clusters are the connected
components of the similar pairs, so grouping stays fast and small when a
camera frame hands back tens of thousands of raw windows.
``group_rectangles_batch`` groups many images' detections in a single pass
(pairs are masked to identical batch ids), producing results identical to
per-image ``group_rectangles`` calls.
"""

from __future__ import annotations

import numpy as np

__all__ = ["group_rectangles", "group_rectangles_batch", "iou_matrix"]


# candidate pairs tested per block: bounds the pairwise temporaries when a
# camera frame hands back tens of thousands of raw windows
_PAIR_BLOCK = 1 << 22


def _similar_pairs(rects: np.ndarray, eps: float,
                   group: np.ndarray | None = None
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Index pairs ``i < j`` joined by OpenCV's SimilarRects predicate.

    delta = eps * (min(w_i, w_j) + min(h_i, h_j)) / 2 and all four edge
    deltas must be within it; with ``group``, only pairs of one group
    join.  A similar ``j`` lies within ``reach_i = eps * (w_i + h_i) / 2``
    of rect ``i`` in both x and y, so rects are sorted by (unit x bin, y)
    and each one is tested only against the y band of every x bin in its
    reach, never against all N.
    """
    x, y, w, h = rects[:, 0], rects[:, 1], rects[:, 2], rects[:, 3]
    reach = eps * (w + h) * 0.5
    pad = 2 * np.ceil(reach.max()) + 4
    shift = np.zeros_like(x)
    if group is not None:   # lay the groups' x bins apart on one axis
        shift = group * (np.floor(x).max() - np.floor(x).min() + pad)
    y0 = y - y.min()
    span = y0.max() + pad   # one x bin's stretch of the sort key
    key = (np.floor(x) + shift) * span + y0
    order = np.argsort(key, kind="stable")
    sorted_key = key[order]
    # one query row per (rect, x bin within its reach), +-1 bin of slack
    b0 = np.floor(x - reach) - 1
    nb = (np.floor(x + reach) + 1 - b0 + 1).astype(np.int64)
    qi = np.repeat(np.arange(len(rects)), nb)
    qb = np.repeat(b0 + shift, nb) + (np.arange(int(nb.sum()))
                                      - np.repeat(np.cumsum(nb) - nb, nb))
    lo = np.searchsorted(sorted_key, qb * span + y0[qi] - reach[qi] - 1,
                         side="left")
    cnt = np.searchsorted(sorted_key, qb * span + y0[qi] + reach[qi] + 1,
                          side="right") - lo
    block = max(1, _PAIR_BLOCK // max(int(cnt.max()), 1))
    pi, pj = [], []
    for s in range(0, len(qi), block):
        c = cnt[s:s + block]
        i = np.repeat(qi[s:s + block], c)
        j = order[np.repeat(lo[s:s + block] - np.cumsum(c) + c, c)
                  + np.arange(int(c.sum()))]
        i, j = i[i < j], j[i < j]
        delta = eps * (np.minimum(w[i], w[j]) + np.minimum(h[i], h[j])) * 0.5
        sim = ((np.abs(x[i] - x[j]) <= delta)
               & (np.abs(y[i] - y[j]) <= delta)
               & (np.abs((x + w)[i] - (x + w)[j]) <= delta)
               & (np.abs((y + h)[i] - (y + h)[j]) <= delta))
        if group is not None:
            sim &= group[i] == group[j]
        pi.append(i[sim])
        pj.append(j[sim])
    return np.concatenate(pi), np.concatenate(pj)


def _cluster_labels(n: int, i: np.ndarray, j: np.ndarray) -> np.ndarray:
    """Connected components of the pair graph: each rect's label is the
    smallest index in its cluster (min-label hooking + pointer jumping)."""
    label = np.arange(n)
    while True:
        new = label.copy()
        m = np.minimum(label[i], label[j])
        np.minimum.at(new, i, m)
        np.minimum.at(new, j, m)
        while True:
            jumped = new[new]
            if np.array_equal(jumped, new):
                break
            new = jumped
        if np.array_equal(new, label):
            return label
        label = new


def _cluster_means(rects: np.ndarray, labels: np.ndarray,
                   min_neighbors: int) -> np.ndarray:
    """Mean rect per kept cluster, clusters in order of their smallest
    member (OpenCV ``groupRectangles`` semantics: a cluster survives iff
    it has *more than* ``min_neighbors`` members, i.e.
    ``>= min_neighbors + 1``; with ``min_neighbors == 0`` every cluster —
    including singletons — is kept)."""
    _, inv, counts = np.unique(labels, return_inverse=True,
                               return_counts=True)
    sums = np.zeros((len(counts), 4))
    np.add.at(sums, inv, rects)
    keep = counts >= min_neighbors + 1
    if not keep.any():
        return np.zeros((0, 4), np.int32)
    return np.rint(sums[keep] / counts[keep, None]).astype(np.int32)


def group_rectangles(rects: np.ndarray, min_neighbors: int = 3,
                     eps: float = 0.2) -> np.ndarray:
    """Cluster (N, 4) [x, y, w, h] rects; return (M, 4) cluster means.

    Mirrors OpenCV semantics: clusters of size < min_neighbors+1 are kept
    only if min_neighbors == 0.
    """
    rects = np.asarray(rects, np.float64).reshape(-1, 4)
    if len(rects) == 0:
        return np.zeros((0, 4), np.int32)
    labels = _cluster_labels(len(rects), *_similar_pairs(rects, eps))
    return _cluster_means(rects, labels, min_neighbors)


def group_rectangles_batch(rects: np.ndarray, batch_idx: np.ndarray,
                           n_batches: int | None = None,
                           min_neighbors: int = 3,
                           eps: float = 0.2) -> list[np.ndarray]:
    """Group many images' rects in one pass.

    ``rects``: (N, 4) concatenated detections; ``batch_idx``: (N,) image id
    per rect.  Returns one (M_b, 4) grouped array per image ``0..n_batches-1``
    — identical to calling :func:`group_rectangles` per image (rect order
    within an image must match the per-image call).
    """
    rects = np.asarray(rects, np.float64).reshape(-1, 4)
    batch_idx = np.asarray(batch_idx, np.int64).reshape(-1)
    if n_batches is None:
        n_batches = int(batch_idx.max()) + 1 if len(batch_idx) else 0
    if len(rects) == 0:
        return [np.zeros((0, 4), np.int32) for _ in range(n_batches)]
    labels = _cluster_labels(len(rects),
                             *_similar_pairs(rects, eps, batch_idx))
    return [_cluster_means(rects[batch_idx == b], labels[batch_idx == b],
                           min_neighbors)
            for b in range(n_batches)]


def iou_matrix(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Pairwise IoU between (N,4) and (M,4) [x,y,w,h] boxes (for eval)."""
    a = np.asarray(a, np.float64).reshape(-1, 4)
    b = np.asarray(b, np.float64).reshape(-1, 4)
    ax1, ay1 = a[:, 0], a[:, 1]
    ax2, ay2 = a[:, 0] + a[:, 2], a[:, 1] + a[:, 3]
    bx1, by1 = b[:, 0], b[:, 1]
    bx2, by2 = b[:, 0] + b[:, 2], b[:, 1] + b[:, 3]
    ix = np.maximum(0, np.minimum(ax2[:, None], bx2[None]) -
                    np.maximum(ax1[:, None], bx1[None]))
    iy = np.maximum(0, np.minimum(ay2[:, None], by2[None]) -
                    np.maximum(ay1[:, None], by1[None]))
    inter = ix * iy
    area_a = (a[:, 2] * a[:, 3])[:, None]
    area_b = (b[:, 2] * b[:, 3])[None]
    return inter / np.maximum(area_a + area_b - inter, 1e-9)
