"""Pallas kernels vs pure-jnp oracle: shape/dtype sweeps (hypothesis).
Under ``JAX_PLATFORMS=cpu`` the kernels run in the Pallas interpreter;
tests/test_tpu_compile.py compiles them for TPU."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.core.integral import integral_images
from repro.core import load_cascade
from repro.configs.viola_jones import DEFAULT_PRETRAINED

CASC, _ = load_cascade(DEFAULT_PRETRAINED)


@settings(max_examples=8, deadline=None)
@given(h=st.integers(25, 140), w=st.integers(25, 180),
       scale=st.sampled_from([1.0, 255.0]))
def test_integral_image_kernel_matches_ref(h, w, scale):
    rng = np.random.default_rng(h * 1000 + w)
    img = jnp.asarray(rng.random((h, w), np.float32) * scale)
    got = ops.integral_image(img, use_kernel=True)
    want = ops.integral_image(img, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-2 * scale)


@settings(max_examples=6, deadline=None)
@given(h=st.integers(30, 100), w=st.integers(30, 120))
def test_window_inv_sigma_kernel_matches_ref(h, w):
    rng = np.random.default_rng(h * 77 + w)
    img = jnp.asarray(rng.integers(0, 255, (h, w)).astype(np.float32))
    _, ii_pair = integral_images(img)
    ny, nx = h - 24 + 1, w - 24 + 1
    got = ops.window_inv_sigma_grid(ii_pair, ny, nx, use_kernel=True)
    want = ops.window_inv_sigma_grid(ii_pair, ny, nx, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("stage", [0, 1])
@pytest.mark.parametrize("hw", [(40, 56), (64, 96)])
def test_haar_stage_kernel_matches_ref(stage, hw):
    if stage >= CASC.n_stages:
        pytest.skip("pretrained cascade has fewer stages")
    h, w = hw
    rng = np.random.default_rng(42)
    img = jnp.asarray(rng.integers(0, 255, (h, w)).astype(np.float32))
    ii, ii_pair = integral_images(img)
    ny, nx = h - 24 + 1, w - 24 + 1
    inv = ops.window_inv_sigma_grid(ii_pair, ny, nx, use_kernel=False)
    got = ops.dense_stage_sums(CASC, CASC, stage, ii, inv)
    want = ops.dense_stage_sums_ref(CASC, CASC, stage, ii, inv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_integral_image_property_last_cell_is_total():
    rng = np.random.default_rng(0)
    img = rng.integers(0, 255, (48, 64)).astype(np.float32)
    ii = np.asarray(ops.integral_image(jnp.asarray(img), use_kernel=True))
    assert abs(ii[-1, -1] - img.sum()) < 1e-2 * img.size
    assert (ii[0] == 0).all() and (ii[:, 0] == 0).all()


# ------------------------------------------------------------------ batched
def _batch_inputs(b, h, w, seed=0):
    rng = np.random.default_rng(seed)
    imgs = jnp.asarray(rng.integers(0, 255, (b, h, w)).astype(np.float32))
    ii, pair = jax.vmap(integral_images)(imgs)
    return imgs, ii, pair


@pytest.mark.parametrize("stage", range(CASC.n_stages))
def test_dense_stage_sums_all_stages_match_ref(stage):
    """Kernel-vs-oracle across *every* cascade stage, on a grid that is not
    tile-aligned in either dimension (ny=17, nx=33 vs the (8, 128) tile)."""
    h, w = 40, 56
    rng = np.random.default_rng(7 * (stage + 1))
    img = jnp.asarray(rng.integers(0, 255, (h, w)).astype(np.float32))
    ii, ii_pair = integral_images(img)
    ny, nx = h - 24 + 1, w - 24 + 1
    inv = ops.window_inv_sigma_grid(ii_pair, ny, nx, use_kernel=False)
    got = ops.dense_stage_sums(CASC, CASC, stage, ii, inv)
    want = ops.dense_stage_sums_ref(CASC, CASC, stage, ii, inv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_integral_image_batch_matches_ref():
    imgs, _, _ = _batch_inputs(3, 37, 61)     # non-tile-aligned H and W
    got = ops.integral_image_batch(imgs, use_kernel=True)
    want = ops.integral_image_batch(imgs, use_kernel=False)
    assert got.shape == (3, 38, 62)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=2.0)
    # per-slice equal to the single-image wrapper (same contract)
    for i in range(3):
        one = ops.integral_image(imgs[i], use_kernel=True)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(one))


def test_window_inv_sigma_batch_matches_ref():
    _, _, pair = _batch_inputs(2, 45, 70, seed=3)
    ny, nx = 45 - 24 + 1, 70 - 24 + 1
    got = ops.window_inv_sigma_grid_batch(pair, ny, nx, use_kernel=True)
    want = ops.window_inv_sigma_grid_batch(pair, ny, nx, use_kernel=False)
    assert got.shape == (2, ny, nx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-6)
    for i in range(2):
        one = ops.window_inv_sigma_grid(pair[i], ny, nx, use_kernel=True)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(one))


@pytest.mark.parametrize("stage", range(CASC.n_stages))
def test_dense_stage_sums_batch_all_stages_match_ref(stage):
    _, ii, pair = _batch_inputs(2, 40, 56, seed=stage)
    ny, nx = 40 - 24 + 1, 56 - 24 + 1
    inv = ops.window_inv_sigma_grid_batch(pair, ny, nx, use_kernel=False)
    got = ops.dense_stage_sums_batch(CASC, CASC, stage, ii, inv)
    want = ops.dense_stage_sums_batch_ref(CASC, CASC, stage, ii, inv)
    assert got.shape == (2, ny, nx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)
    # each slice bit-equal to the single-image kernel (batch = vmap of it)
    for i in range(2):
        one = ops.dense_stage_sums(CASC, CASC, stage, ii[i], inv[i])
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(one))


# ---------------------------------------------------------------- oracles
# Direct kernel-vs-oracle races (repro.analysis KERNEL_REF_TEST contract:
# every public kernel must be checked against its *_ref twin by name, not
# only through the use_kernel=False convenience path).

def test_integral_image_vs_oracle_twin():
    rng = np.random.default_rng(7)
    img = jnp.asarray(rng.integers(0, 255, (48, 72)).astype(np.float32))
    got = ops.integral_image(img, use_kernel=True)
    want = jnp.pad(ref.integral_image_ref(img), ((1, 0), (1, 0)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-2)


def test_integral_image_batch_vs_oracle_twin():
    rng = np.random.default_rng(11)
    imgs = jnp.asarray(rng.integers(0, 255, (3, 40, 56)).astype(np.float32))
    got = ops.integral_image_batch(imgs, use_kernel=True)
    want = jnp.pad(ref.integral_image_batch_ref(imgs),
                   ((0, 0), (1, 0), (1, 0)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-2)


def test_window_inv_sigma_grid_vs_oracle_twin():
    rng = np.random.default_rng(13)
    img = jnp.asarray(rng.integers(0, 255, (52, 68)).astype(np.float32))
    _, ii_pair = integral_images(img)
    ny, nx = 52 - 24 + 1, 68 - 24 + 1
    got = ops.window_inv_sigma_grid(ii_pair, ny, nx, use_kernel=True)
    want = ref.window_inv_sigma_grid_ref(ii_pair, ny, nx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


def test_window_inv_sigma_grid_batch_vs_oracle_twin():
    rng = np.random.default_rng(17)
    imgs = rng.integers(0, 255, (2, 44, 60)).astype(np.float32)
    pairs = jnp.stack([integral_images(jnp.asarray(im))[1] for im in imgs])
    ny, nx = 44 - 24 + 1, 60 - 24 + 1
    got = ops.window_inv_sigma_grid_batch(pairs, ny, nx, use_kernel=True)
    want = ref.window_inv_sigma_grid_batch_ref(pairs, ny, nx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


# ------------------------------------------------------------------ fused
N_RUN = min(3, CASC.n_stages)     # the megakernel's stage run
TH = np.asarray(CASC.stage_threshold[:N_RUN])


def _thresholds(casc, th):
    """``casc`` with its first stages' thresholds set to ``th``."""
    full = np.asarray(casc.stage_threshold).copy()
    full[:len(th)] = th
    return casc._replace(stage_threshold=jnp.asarray(full, jnp.float32))


def _split_sums(c, im):
    """The split path: jnp SAT and 1/sigma, one haar_stage dispatch per
    stage, every stage on every window (no exit)."""
    from repro.core.integral import window_inv_sigma

    ny, nx = im.shape[0] - 24 + 1, im.shape[1] - 24 + 1
    ii, pair = integral_images(im)
    inv = window_inv_sigma(pair, jnp.arange(ny)[:, None],
                           jnp.arange(nx)[None, :], 24)
    sums = jnp.stack([ops.dense_stage_sums(c, CASC, s, ii, inv)
                      for s in range(N_RUN)])
    return ii, inv, sums


def _alive_chain(sums, th):
    """The engine's cumulative survivors after each stage."""
    alive = np.ones(sums.shape[1:], bool)
    out = []
    for s in range(sums.shape[0]):
        alive = alive & (sums[s] >= th[s])
        out.append(alive)
    return np.stack(out)


def test_fused_head_vs_oracle_twin():
    """ops.fused_head vs ref.fused_head_ref on a non-tile-aligned grid
    (ny=17, nx=33), all three outputs; the twin skips the same tiles."""
    h, w = 40, 56
    rng = np.random.default_rng(23)
    img = jnp.asarray(rng.integers(0, 255, (h, w)).astype(np.float32))
    ii, inv, sums = ops.fused_head(CASC, CASC, 0, N_RUN, img)
    ii_r, inv_r, sums_r = ops.fused_head_ref(CASC, CASC, 0, N_RUN, img)
    assert sums.shape == (N_RUN, h - 24 + 1, w - 24 + 1)
    np.testing.assert_allclose(np.asarray(ii), np.asarray(ii_r),
                               rtol=1e-5, atol=1e-2)
    np.testing.assert_allclose(np.asarray(inv), np.asarray(inv_r),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.isneginf(sums), np.isneginf(sums_r))
    np.testing.assert_allclose(np.asarray(sums), np.asarray(sums_r),
                               rtol=1e-4, atol=1e-3)
    # the module-level oracle twin is the same function ops re-exports
    ii_m, inv_m, sums_m = ref.fused_head_ref(
        CASC.rect_xywh[:CASC.stage_offsets[N_RUN]],
        CASC.rect_w[:CASC.stage_offsets[N_RUN]],
        CASC.wc_threshold[:CASC.stage_offsets[N_RUN]],
        CASC.left_val[:CASC.stage_offsets[N_RUN]],
        CASC.right_val[:CASC.stage_offsets[N_RUN]],
        CASC.stage_threshold[:N_RUN],
        tuple(int(b) for b in CASC.stage_offsets[:N_RUN + 1]), img)
    np.testing.assert_array_equal(np.asarray(sums_r), np.asarray(sums_m))


def test_fused_head_batch_vs_oracle_twin():
    rng = np.random.default_rng(29)
    imgs = jnp.asarray(rng.integers(0, 255, (3, 40, 56)).astype(np.float32))
    ii, inv, sums = ops.fused_head_batch(CASC, CASC, 0, N_RUN, imgs)
    ii_r, inv_r, sums_r = ops.fused_head_batch_ref(CASC, CASC, 0, N_RUN,
                                                   imgs)
    assert sums.shape == (3, N_RUN, 40 - 24 + 1, 56 - 24 + 1)
    np.testing.assert_allclose(np.asarray(ii), np.asarray(ii_r),
                               rtol=1e-5, atol=1e-2)
    np.testing.assert_allclose(np.asarray(inv), np.asarray(inv_r),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.isneginf(sums), np.isneginf(sums_r))
    np.testing.assert_allclose(np.asarray(sums), np.asarray(sums_r),
                               rtol=1e-4, atol=1e-3)
    assert "fused_head_batch_ref" in dir(ref)
    # each slice bit-equal to the single-image kernel (batch = vmap of it)
    for i in range(3):
        one = ops.fused_head(CASC, CASC, 0, N_RUN, imgs[i])
        for got_b, want_b in zip((ii[i], inv[i], sums[i]), one):
            np.testing.assert_array_equal(np.asarray(got_b),
                                          np.asarray(want_b))


@pytest.mark.parametrize("hw", [(40, 56), (25, 25), (31, 140)])
def test_fused_head_bit_identical_to_split_path(hw):
    """The engine's bit-exactness contract: under jit, the fused megakernel
    reproduces the split three-dispatch path (jnp SAT + jnp 1/sigma + one
    haar_stage dispatch per stage) to the last ulp wherever the window's
    tile entered the stage, and reads -inf wherever it did not, on
    tile-aligned and non-tile-aligned grids alike; the engine's survivors
    after every stage are the split path's."""
    h, w = hw
    rng = np.random.default_rng(h * 31 + w)
    img = jnp.asarray(rng.integers(0, 255, (h, w)).astype(np.float32))

    def fused(c, im):
        return ops.fused_head(c, CASC, 0, N_RUN, im)

    want = jax.jit(_split_sums)(CASC, img)
    got = jax.jit(fused)(CASC, img)
    for g, wnt in zip(got[:2], want[:2]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(wnt))
    np.testing.assert_array_equal(
        np.asarray(got[2]), np.asarray(ref.tile_exit_ref(want[2], TH)))
    np.testing.assert_array_equal(_alive_chain(np.asarray(got[2]), TH),
                                  _alive_chain(np.asarray(want[2]), TH))


@pytest.mark.parametrize("hw", [(40, 56), (31, 140)])
def test_fused_head_thresholds_that_reject_nothing_run_dense(hw):
    """Thresholds at -inf reject no window: every tile runs every stage,
    and the output is the dense split path's, bit for bit."""
    h, w = hw
    rng = np.random.default_rng(h + w)
    img = jnp.asarray(rng.integers(0, 255, (h, w)).astype(np.float32))
    open_c = _thresholds(CASC, [-np.inf] * N_RUN)
    want = jax.jit(_split_sums)(open_c, img)
    got = jax.jit(lambda c, im: ops.fused_head(c, CASC, 0, N_RUN, im))(
        open_c, img)
    assert not np.isinf(np.asarray(got[2])).any()
    for g, wnt in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(wnt))


def test_fused_head_thresholds_that_reject_everything_run_stage_0():
    """Thresholds at +inf: every tile runs stage 0, whose sums are the
    split path's, and writes -inf for every later stage."""
    rng = np.random.default_rng(41)
    img = jnp.asarray(rng.integers(0, 255, (40, 160)).astype(np.float32))
    shut = _thresholds(CASC, [np.inf] * N_RUN)
    want = _split_sums(shut, img)[2]
    got = np.asarray(ops.fused_head(shut, CASC, 0, N_RUN, img)[2])
    np.testing.assert_array_equal(got[0], np.asarray(want[0]))
    assert np.isneginf(got[1:]).all()


def test_fused_head_padded_edge_windows_keep_no_tile_alive():
    """On a grid that is not tile-aligned (ny=17, nx=33), the kernel's
    padded window origins would pass stage 0 at this threshold while no
    real window does: the tiles still exit after stage 0."""
    from repro.core.integral import window_inv_sigma
    from repro.kernels.autotune import DEFAULT_TILE
    from repro.kernels.haar_stage import (haar_stage_sums_kernel,
                                          sat_pad_shape)
    from repro.kernels.platform import interpret_mode

    h, w = 40, 56
    ny, nx = h - 24 + 1, w - 24 + 1
    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.integers(0, 255, (h, w)).astype(np.float32))
    # stage 0's sums over the padded grid, as the kernel's tiles see it
    ii, pair = integral_images(img)
    inv = window_inv_sigma(pair, jnp.arange(ny)[:, None],
                           jnp.arange(nx)[None, :], 24)
    ty, tx = DEFAULT_TILE
    ny_pad, nx_pad = ny + (-ny) % ty, nx + (-nx) % tx
    hp, wp = sat_pad_shape(ny_pad, nx_pad)
    k1 = int(CASC.stage_offsets[1])
    padded = np.asarray(haar_stage_sums_kernel(
        CASC.rect_xywh[:k1], CASC.rect_w[:k1], CASC.wc_threshold[:k1],
        CASC.left_val[:k1], CASC.right_val[:k1],
        jnp.pad(ii, ((0, hp - h - 1), (0, wp - w - 1)), mode="edge"),
        jnp.pad(inv, ((0, ny_pad - ny), (0, nx_pad - nx)), mode="edge"),
        interpret=interpret_mode()))
    real = np.zeros(padded.shape, bool)
    real[:ny, :nx] = True
    th0 = (padded[real].max() + padded[~real].max()) / 2
    assert padded[real].max() < th0 <= padded[~real].max()

    got = np.asarray(ops.fused_head(_thresholds(CASC, [th0]), CASC, 0, N_RUN,
                                    img)[2])
    np.testing.assert_array_equal(got[0], padded[:ny, :nx])
    assert np.isneginf(got[1:]).all()


def test_fused_head_batch_images_exit_independently():
    """A flat image (every window rejected at stage 0) beside a textured
    one: each image of the batch skips its own tiles, and each equals the
    single-image call bit for bit."""
    rng = np.random.default_rng(43)
    textured = rng.integers(0, 255, (40, 160)).astype(np.float32)
    imgs = jnp.asarray(np.stack([textured, np.full_like(textured, 90.0)]))
    flat0 = np.asarray(_split_sums(CASC, imgs[1])[2])[0].max()
    casc = _thresholds(CASC, [max(TH[0], np.nextafter(flat0, np.inf)),
                              -np.inf, -np.inf])
    _, _, sums = ops.fused_head_batch(casc, CASC, 0, N_RUN, imgs)
    sums = np.asarray(sums)
    assert np.isneginf(sums[1, 1:]).all()
    assert not np.isneginf(sums[0, 1]).all()
    for i in range(2):
        one = ops.fused_head(casc, CASC, 0, N_RUN, imgs[i])[2]
        np.testing.assert_array_equal(sums[i], np.asarray(one))


@pytest.mark.parametrize("tile", [(16, 128), (8, 256)])
def test_fused_head_tile_shape_does_not_change_bits(tile):
    """Autotuned block shapes are bit-exact-safe by construction: every
    per-window operation is elementwise over the tile, so racing candidate
    shapes never changes a sum a tile computes, nor the survivors; only
    which stages read -inf follows the tile."""
    rng = np.random.default_rng(37)
    img = jnp.asarray(rng.integers(0, 255, (40, 56)).astype(np.float32))
    dense = _split_sums(CASC, img)[2]
    base = ops.fused_head(CASC, CASC, 0, N_RUN, img)
    other = ops.fused_head(CASC, CASC, 0, N_RUN, img, tile=tile)
    for g, wnt in zip(other[:2], base[:2]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(wnt))
    np.testing.assert_array_equal(
        np.asarray(other[2]),
        np.asarray(ref.tile_exit_ref(dense, TH, tile)))
    np.testing.assert_array_equal(_alive_chain(np.asarray(other[2]), TH),
                                  _alive_chain(np.asarray(base[2]), TH))


@pytest.mark.parametrize("mask", ["none", "all", "random"])
def test_masked_fused_head_vs_oracle_twin(mask):
    """The masked fused head starts each tile from its live windows: an
    all-false map runs no stage anywhere, an all-true map is the unmasked
    call bit for bit, and a random map matches the oracle twin and the
    split path's sums under the same tile exit."""
    h, w = 40, 160                      # two (8, 128) tile columns
    rng = np.random.default_rng(59)
    img = jnp.asarray(rng.integers(0, 255, (h, w)).astype(np.float32))
    ny, nx = h - 24 + 1, w - 24 + 1
    live = {"none": np.zeros((ny, nx), bool),
            "all": np.ones((ny, nx), bool),
            "random": rng.random((ny, nx)) < 0.002}[mask]

    def masked(c, im, lv):
        return ops.fused_head(c, CASC, 0, N_RUN, im, live=lv)

    got = jax.jit(masked)(CASC, img, jnp.asarray(live))
    plain = jax.jit(lambda c, im: ops.fused_head(c, CASC, 0, N_RUN, im))(
        CASC, img)
    want = ops.fused_head_ref(CASC, CASC, 0, N_RUN, img, live=live)
    for g, p in zip(got[:2], plain[:2]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(p))
    np.testing.assert_array_equal(np.isneginf(got[2]), np.isneginf(want[2]))
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=1e-4, atol=1e-3)
    split = jax.jit(_split_sums)(CASC, img)[2]
    np.testing.assert_array_equal(
        np.asarray(got[2]),
        np.asarray(ref.tile_exit_ref(split, TH, live=jnp.asarray(live))))
    if mask == "none":
        assert np.isneginf(np.asarray(got[2])).all()
    if mask == "all":
        np.testing.assert_array_equal(np.asarray(got[2]),
                                      np.asarray(plain[2]))
    if mask == "random":                # some tiles ran, some did not
        ran = ~np.isneginf(np.asarray(got[2][0, ::8, ::128]))
        assert ran.any() and not ran.all()


def test_int32_tables_are_exact_where_float32_rounds():
    """Past 2^24 a float32 table rounds; the int32 tables are exact at any
    frame size: on a 512 x 512 all-255 frame the bottom-right window sums
    576 * 255 and every window's 1/sigma is 1 (a flat window), and on a
    random frame a window's 1/sigma and stage sums (``weak_vote``) are
    those of the same pixels cropped out — window-locality — where the
    float32 table's rectangle sums are not."""
    from repro.core.integral import rect_sum, window_inv_sigma_grid

    flat = jnp.full((512, 512), 255.0, jnp.float32)
    ii, pair = integral_images(flat)
    assert ii.dtype == jnp.int32 and pair.dtype == jnp.int32
    assert float(rect_sum(ii, 488, 488, 24, 24)) == 576 * 255
    inv = window_inv_sigma_grid(pair, 489, 489, 1, 24)
    assert (np.asarray(inv) == 1.0).all()

    rng = np.random.default_rng(61)
    big = rng.integers(0, 256, (512, 512)).astype(np.float32)
    crop = big[-40:, -56:]
    f32 = np.pad(np.cumsum(np.cumsum(big, 0, dtype=np.float32), 1,
                           dtype=np.float32), ((1, 0), (1, 0)))
    exact = np.pad(np.cumsum(np.cumsum(big.astype(np.int64), 0), 1),
                   ((1, 0), (1, 0)))
    ys, xs = np.mgrid[489 - 17:489, 489 - 33:489]
    f32_sums = (f32[ys + 24, xs + 24] - f32[ys, xs + 24]
                - f32[ys + 24, xs] + f32[ys, xs])
    exact_sums = (exact[ys + 24, xs + 24] - exact[ys, xs + 24]
                  - exact[ys + 24, xs] + exact[ys, xs])
    assert (f32_sums != exact_sums).any()     # the float32 table rounds
    ii_b, pair_b = integral_images(jnp.asarray(big))
    np.testing.assert_array_equal(
        np.asarray(rect_sum(ii_b, jnp.asarray(ys), jnp.asarray(xs), 24,
                            24)), exact_sums.astype(np.float32))
    ii_c, pair_c = integral_images(jnp.asarray(crop))
    inv_b = window_inv_sigma_grid(pair_b, 489, 489, 1, 24)[-17:, -33:]
    inv_c = window_inv_sigma_grid(pair_c, 17, 33, 1, 24)
    np.testing.assert_array_equal(np.asarray(inv_b), np.asarray(inv_c))
    # weak_vote through the split kernel on the bottom-right windows of
    # the whole frame's table, against the crop's own table
    pad_b = ii_b[-41:, -57:]
    sums_b = ops.dense_stage_sums(CASC, CASC, 0, pad_b, inv_b)
    sums_c = ops.dense_stage_sums(CASC, CASC, 0, ii_c, inv_c)
    np.testing.assert_array_equal(np.asarray(sums_b), np.asarray(sums_c))
