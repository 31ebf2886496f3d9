"""Regression tests for detection grouping: golden clusters, min_neighbors
edge cases at 0/1, transitive chaining, and the batched variant's exact
equivalence to per-image grouping."""

import numpy as np
import pytest

from repro.core import group_rectangles, group_rectangles_batch

# Golden fixture: two real clusters + one outlier.
CLUSTER_A = np.asarray([
    [10, 10, 20, 20],
    [11, 10, 20, 20],
    [10, 12, 20, 20],
    [12, 11, 20, 20],
])
CLUSTER_B = np.asarray([
    [50, 50, 24, 24],
    [51, 52, 24, 24],
    [49, 50, 24, 24],
])
OUTLIER = np.asarray([[100, 100, 10, 10]])
RECTS = np.concatenate([CLUSTER_A, CLUSTER_B, OUTLIER])


def test_golden_clusters_min_neighbors_2():
    """mn=2 keeps clusters with > 2 members: A (4) and B (3), not the
    singleton outlier."""
    got = group_rectangles(RECTS, min_neighbors=2)
    want = np.rint(np.stack([CLUSTER_A.mean(axis=0).astype(np.float64),
                             CLUSTER_B.mean(axis=0).astype(np.float64)])
                   ).astype(np.int32)
    assert np.array_equal(got, want)


def test_min_neighbors_3_drops_exact_size_cluster():
    """OpenCV parity: groupRectangles keeps a cluster iff its size is
    *strictly greater* than groupThreshold — a cluster of exactly
    ``min_neighbors`` members (B, 3 rects at mn=3) must be dropped."""
    got = group_rectangles(RECTS, min_neighbors=3)
    want = np.rint(CLUSTER_A.mean(axis=0)).astype(np.int32)[None]
    assert np.array_equal(got, want)


def test_min_neighbors_4_drops_exact_size_cluster():
    """A cluster of exactly min_neighbors members (A, 4 rects at mn=4) is
    dropped too — nothing survives."""
    got = group_rectangles(RECTS, min_neighbors=4)
    assert got.shape == (0, 4)


def test_min_neighbors_0_keeps_everything():
    """mn=0 keeps every cluster including singletons (size >= 1)."""
    got = group_rectangles(RECTS, min_neighbors=0)
    assert len(got) == 3                     # A, B, and the outlier cluster
    assert np.rint(OUTLIER[0]).astype(np.int32).tolist() in got.tolist()


def test_min_neighbors_1_drops_singletons():
    """mn=1 requires >= 2 members: the singleton outlier is dropped."""
    got = group_rectangles(RECTS, min_neighbors=1)
    assert len(got) == 2
    assert np.rint(OUTLIER[0]).astype(np.int32).tolist() not in got.tolist()


def test_empty_input():
    got = group_rectangles(np.zeros((0, 4)), min_neighbors=3)
    assert got.shape == (0, 4) and got.dtype == np.int32


def test_transitive_chaining_forms_one_cluster():
    """a~b and b~c but a!~c still union into a single cluster."""
    chain = np.asarray([[0, 0, 20, 20], [4, 0, 20, 20], [8, 0, 20, 20]])
    got = group_rectangles(chain, min_neighbors=2)
    assert len(got) == 1
    assert np.array_equal(got[0], np.rint(chain.mean(axis=0)).astype(np.int32))
    # ...but the 3-member chain does not survive mn=3 (needs > 3 members)
    assert group_rectangles(chain, min_neighbors=3).shape == (0, 4)


# ------------------------------------------------------------------ batched
def test_batched_matches_per_image_golden():
    rects = np.concatenate([RECTS, RECTS + 3])
    batch_idx = np.concatenate([np.zeros(len(RECTS), int),
                                np.ones(len(RECTS), int)])
    got = group_rectangles_batch(rects, batch_idx, min_neighbors=3)
    assert len(got) == 2
    for b in range(2):
        want = group_rectangles(rects[batch_idx == b], min_neighbors=3)
        assert np.array_equal(got[b], want)


@pytest.mark.parametrize("mn", [0, 1, 2, 3])
def test_batched_matches_per_image_random(mn):
    rng = np.random.default_rng(42)
    n, n_batches = 60, 4
    rects = np.stack([rng.integers(0, 80, n), rng.integers(0, 80, n),
                      rng.integers(10, 30, n), rng.integers(10, 30, n)],
                     axis=1)
    batch_idx = rng.integers(0, n_batches, n)
    got = group_rectangles_batch(rects, batch_idx, n_batches=n_batches,
                                 min_neighbors=mn)
    assert len(got) == n_batches
    for b in range(n_batches):
        want = group_rectangles(rects[batch_idx == b], min_neighbors=mn)
        assert np.array_equal(got[b], want)


def test_batched_never_merges_across_images():
    """Identical rects on different images must stay separate clusters."""
    rects = np.concatenate([CLUSTER_A, CLUSTER_A])
    batch_idx = np.concatenate([np.zeros(4, int), np.ones(4, int)])
    got = group_rectangles_batch(rects, batch_idx, min_neighbors=3)
    for b in range(2):
        assert len(got[b]) == 1
        assert np.array_equal(got[b][0],
                              np.rint(CLUSTER_A.mean(axis=0)).astype(np.int32))


def test_batched_empty():
    got = group_rectangles_batch(np.zeros((0, 4)), np.zeros(0, int),
                                 n_batches=3)
    assert len(got) == 3
    assert all(g.shape == (0, 4) for g in got)


# ------------------------------------------------------- pairwise reference
def _reference_groups(rects, min_neighbors, eps=0.2):
    """OpenCV groupRectangles by brute force: the SimilarRects predicate on
    every pair, then union-find; clusters as a sorted list of mean rects."""
    r = np.asarray(rects, np.float64)
    n = len(r)
    parent = list(range(n))

    def find(i):
        while parent[i] != i:
            i = parent[i]
        return i

    for i in range(n):
        for j in range(i + 1, n):
            delta = eps * (min(r[i, 2], r[j, 2]) + min(r[i, 3], r[j, 3])) / 2
            if (abs(r[i, 0] - r[j, 0]) <= delta
                    and abs(r[i, 1] - r[j, 1]) <= delta
                    and abs(r[i, 0] + r[i, 2] - r[j, 0] - r[j, 2]) <= delta
                    and abs(r[i, 1] + r[i, 3] - r[j, 1] - r[j, 3]) <= delta):
                parent[find(j)] = find(i)
    roots = np.asarray([find(i) for i in range(n)])
    return sorted(tuple(np.rint(r[roots == c].mean(axis=0)).astype(int))
                  for c in np.unique(roots)
                  if (roots == c).sum() >= min_neighbors + 1)


@pytest.mark.parametrize("mn", [0, 1, 3])
@pytest.mark.parametrize("seed", [0, 1])
def test_matches_pairwise_reference(seed, mn):
    """Banded pair search == all-pairs predicate, on integer window grids
    at pyramid scales and on arbitrary float rects."""
    rng = np.random.default_rng(seed)
    n = 300
    s = rng.choice([24, 29, 35, 41, 50, 60], n)
    grid = np.stack([rng.integers(0, 200, n), rng.integers(0, 200, n), s, s],
                    axis=1)
    floats = np.concatenate([rng.uniform(-50, 300, (n, 2)),
                             np.repeat(rng.uniform(5, 80, (n, 1)), 2, axis=1)
                             * rng.uniform(0.8, 1.2, (n, 2))], axis=1)
    for rects in (grid, floats):
        got = sorted(map(tuple, group_rectangles(rects, min_neighbors=mn)))
        assert got == _reference_groups(rects, mn)


def test_dense_camera_frame_scale():
    """A camera frame's worth of raw windows (every origin of a 150x200
    grid, plus a coarser scale) groups without an (N, N) matrix: one
    chained cluster per scale."""
    ys, xs = np.mgrid[0:150, 0:200]
    fine = np.stack([xs.ravel(), ys.ravel(), np.full(xs.size, 24),
                     np.full(xs.size, 24)], axis=1)
    coarse = np.stack([xs.ravel()[::4] * 2, ys.ravel()[::4] * 2,
                       np.full(xs.size // 4, 48), np.full(xs.size // 4, 48)],
                      axis=1)
    rects = np.concatenate([fine, coarse])
    assert len(rects) == 37_500
    got = group_rectangles(rects, min_neighbors=3)
    assert len(got) == 2
