"""Kernel placement taken from the JAX backend: interpret mode, the
Pallas default and the engine mode (``repro.kernels.platform``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Detector, EngineConfig, paper_shaped_cascade
from repro.core.features import stage_sum_grid, stage_sum_windows
from repro.core.integral import (integral_images, window_inv_sigma,
                                 window_inv_sigma_grid)
from repro.kernels import platform

CASC = paper_shaped_cascade(0, stage_sizes=[3, 4, 5])


@pytest.mark.parametrize("backend, interpret, kernels, mode", [
    ("cpu", True, False, "wave"),
    ("tpu", False, True, "dense"),
])
def test_defaults_follow_backend(monkeypatch, backend, interpret, kernels,
                                 mode):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert platform.interpret_mode() is interpret
    assert platform.kernels_by_default() is kernels
    assert platform.mode_by_default() == mode
    cfg = Detector(CASC, EngineConfig()).config
    assert (cfg.use_pallas, cfg.mode) == (kernels, mode)
    # an explicit choice is kept on every platform
    cfg = Detector(CASC, EngineConfig(use_pallas=False, mode="wave")).config
    assert (cfg.use_pallas, cfg.mode) == (False, "wave")


def test_kernels_refuse_other_backends(monkeypatch):
    """No silent interpreter on a backend the kernels were not built for."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        platform.interpret_mode()


@pytest.mark.parametrize("step", [1, 2])
def test_grid_evaluators_bit_identical_to_gathers(step):
    """The slice-based dense-grid evaluators equal the gather forms to
    the last bit (same elements, same float ordering)."""
    rng = np.random.default_rng(step)
    img = jnp.asarray(rng.integers(0, 256, (61, 83)).astype(np.float32))
    ii, pair = integral_images(img)
    ny, nx = (61 - 24) // step + 1, (83 - 24) // step + 1
    gy = jnp.arange(ny) * step
    gx = jnp.arange(nx) * step
    inv = window_inv_sigma(pair, gy[:, None], gx[None, :], 24)
    inv_grid = window_inv_sigma_grid(pair, ny, nx, step, 24)
    np.testing.assert_array_equal(np.asarray(inv_grid), np.asarray(inv))
    ys = jnp.repeat(gy, nx)
    xs = jnp.tile(gx, ny)
    k0, k1 = 0, int(CASC.stage_offsets[-1])
    want = stage_sum_windows(CASC, ii, ys, xs, inv.reshape(-1), k0, k1)
    got = stage_sum_grid(CASC, ii, inv_grid, step, k0, k1)
    np.testing.assert_array_equal(np.asarray(got).reshape(-1),
                                  np.asarray(want))
