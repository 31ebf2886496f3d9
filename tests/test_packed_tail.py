"""The shared packed-tail evaluator: three backends, one bit-level truth.

``packed_tail.stage_sums`` is the single implementation behind the batched
engine's shared-compaction segments and the streaming engine's incremental
tail; these tests pin (a) bit-identity of the bulk-gather and Pallas
packed-window backends to the fori-loop gather oracle on multi-image,
multi-level packed lists at non-rung-aligned sizes, (b) the kernel wrapper
against its ``ref.py`` twin, and (c) the crossover ladder policy
(``select_backend`` / ``measure_rungs``) that picks a backend per capacity
rung."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import EngineConfig, paper_shaped_cascade
from repro.core.cascade import WINDOW
from repro.core.integral import integral_images, window_inv_sigma
from repro.kernels import ops, packed_tail

CASC = paper_shaped_cascade(0, stage_sizes=[3, 4, 5, 6, 8])
N_STAGES = CASC.n_stages


def _packed_workload(cap: int, seed: int = 0):
    """A packed window list spanning 2 images x 2 pyramid-level shapes,
    with real SATs and real per-window normalization."""
    rng = np.random.default_rng(seed)
    levels = [(72, 88), (48, 56)]
    sats, pair_tabs, bases, strides = [], [], [], []
    base = 0
    for h, w in levels:
        imgs = rng.integers(0, 255, (2, h, w)).astype(np.float32)
        ii = np.stack([np.asarray(integral_images(jnp.asarray(im))[0])
                       for im in imgs])
        pr = [integral_images(jnp.asarray(im))[1] for im in imgs]
        sats.append(ii.reshape(2, -1))
        pair_tabs.append(pr)
        bases.append(base)
        strides.append(w + 1)
        base += (h + 1) * (w + 1)
    ii_flat = jnp.asarray(np.concatenate(sats, axis=1))
    lv = rng.integers(0, len(levels), cap)
    img = rng.integers(0, 2, cap).astype(np.int32)
    ys = np.asarray([rng.integers(0, levels[v][0] - WINDOW + 1)
                     for v in lv], np.int32)
    xs = np.asarray([rng.integers(0, levels[v][1] - WINDOW + 1)
                     for v in lv], np.int32)
    b = np.asarray([bases[v] for v in lv], np.int32)
    st = np.asarray([strides[v] for v in lv], np.int32)
    inv = np.asarray([np.asarray(window_inv_sigma(
        pair_tabs[lv[i]][img[i]], jnp.asarray(ys[i]), jnp.asarray(xs[i]),
        WINDOW)) for i in range(cap)], np.float32)
    return (ii_flat, jnp.asarray(img), jnp.asarray(b), jnp.asarray(st),
            jnp.asarray(ys), jnp.asarray(xs), jnp.asarray(inv))


WORKLOAD = _packed_workload(317)          # odd: exercises lane-block padding


# ----------------------------------------------------------- bit identity
@pytest.mark.parametrize("backend", ["bulk", "pallas"])
def test_backends_match_gather_oracle(backend):
    want = np.asarray(packed_tail.stage_sums(
        CASC, CASC, 0, N_STAGES, *WORKLOAD, backend="gather"))
    got = np.asarray(packed_tail.stage_sums(
        CASC, CASC, 0, N_STAGES, *WORKLOAD, backend=backend))
    assert got.shape == (N_STAGES, 317)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("cap", [5, 128, 1024])
def test_pallas_rung_alignment(cap):
    """Exactly one lane-block, below it, and a non-multiple above it."""
    wl = _packed_workload(cap, seed=cap)
    want = np.asarray(packed_tail.stage_sums(
        CASC, CASC, 0, N_STAGES, *wl, backend="gather"))
    got = np.asarray(packed_tail.stage_sums(
        CASC, CASC, 0, N_STAGES, *wl, backend="pallas"))
    assert np.array_equal(got, want)


def test_kernel_wrapper_matches_ref_twin():
    got = np.asarray(ops.packed_stage_sums(
        CASC, CASC, 1, N_STAGES, *WORKLOAD))
    want = np.asarray(ops.packed_stage_sums_ref(
        CASC, CASC, 1, N_STAGES, *WORKLOAD))
    assert got.shape == want.shape == (N_STAGES - 1, 317)
    assert np.array_equal(got, want)


def test_stage_run_rows_equal_per_stage_calls():
    """A [s0, s1) run is exactly the stack of single-stage evaluations —
    the contract that lets engines call once per segment."""
    run = np.asarray(packed_tail.stage_sums(
        CASC, CASC, 1, 4, *WORKLOAD, backend="pallas"))
    for j, s in enumerate(range(1, 4)):
        one = np.asarray(packed_tail.stage_sums(
            CASC, CASC, s, s + 1, *WORKLOAD, backend="gather"))
        assert np.array_equal(run[j], one[0])


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown packed-tail backend"):
        # repro: ignore[TAIL_BACKEND] negative test: exercises the unknown-backend rejection path
        packed_tail.stage_sums(CASC, CASC, 0, 1, *WORKLOAD, backend="nope")


# ------------------------------------------------------------- the ladder
def test_select_backend_forced_and_auto():
    forced = EngineConfig(tail_backend="pallas")
    assert packed_tail.select_backend(forced, 1) == "pallas"
    empty = EngineConfig(tail_backend="auto")
    assert packed_tail.select_backend(empty, 10_000) == "bulk"
    ladder = EngineConfig(tail_backend="auto", tail_rungs=(
        (128, "gather"), (1024, "bulk"), (8192, "pallas")))
    assert packed_tail.select_backend(ladder, 1) == "gather"
    assert packed_tail.select_backend(ladder, 128) == "gather"   # inclusive
    assert packed_tail.select_backend(ladder, 129) == "bulk"
    assert packed_tail.select_backend(ladder, 5000) == "pallas"
    assert packed_tail.select_backend(ladder, 10**6) == "pallas"  # beyond


def test_measure_rungs_shape():
    small = paper_shaped_cascade(1, stage_sizes=[2, 3])
    prof = packed_tail.measure_rungs(small, sizes=(64, 256), repeats=1,
                                     inner=2)
    assert prof["sizes"] == [64, 256]
    assert prof["n_windows"] > 0
    assert set(prof["ms"]) == set(packed_tail.BACKENDS)
    assert all(len(v) == 2 and all(t > 0 for t in v)
               for v in prof["ms"].values())
    assert len(prof["rungs"]) == 2
    assert all(bk in packed_tail.BACKENDS for _n, bk in prof["rungs"])
    assert prof["crossover"] == -1 or prof["crossover"] in prof["sizes"]
