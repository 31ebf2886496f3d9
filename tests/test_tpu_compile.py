"""Compile rehearsals: the main-path Pallas kernels compiled by Mosaic for
a TPU v5e, at level 0 of a 640x480 frame with the paper cascade.

No chip is needed: the v5e is *described* (``topologies``) and each
kernel is lowered and compiled for it with ``interpret=False`` while the
process's own backend stays the CPU.  A kernel Mosaic cannot lower fails
here, before any chip time is spent on it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.viola_jones import paper_cascade
from repro.core.cascade import WINDOW
from repro.kernels import packed_window
from repro.kernels.autotune import DEFAULT_TILE
from repro.kernels.fused_head import fused_head_kernel
from repro.kernels.haar_stage import haar_stage_sums_kernel, sat_pad_shape
from repro.kernels.packed_window import (MOSAIC_REFUSAL,
                                         packed_stage_sums_kernel)

H, W = 480, 640                          # VGA level 0
NY, NX = H - WINDOW + 1, W - WINDOW + 1
CASC = paper_cascade()
OFF = np.asarray(CASC.stage_offsets)
DENSE_PREFIX = 3                         # the wave plan's dense head at VGA
TAIL_RUN = (3, 6)                        # the first packed-tail segment


@pytest.fixture(scope="module")
def v5e():
    """A sharding on one described v5e device (skips without libtpu)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                       # noqa: BLE001
            pytest.skip(f"no TPU topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(dev, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)


def _weak_specs(dev, k0, k1):
    n = k1 - k0
    return [_spec(dev, (n, 3, 4), jnp.int32), _spec(dev, (n, 3), jnp.float32),
            _spec(dev, (n,), jnp.float32), _spec(dev, (n,), jnp.float32),
            _spec(dev, (n,), jnp.float32)]


def _compile(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n_stages", [DENSE_PREFIX, CASC.n_stages],
                         ids=["wave_head", "dense_all_stages"])
def test_fused_head_compiles_for_v5e(v5e, n_stages):
    """The wave plan's dense head, and dense mode's whole cascade (the
    TPU default), whose weak-classifier tables must fit SMEM; the stage
    loop with its per-tile exit lowers through Mosaic."""
    rel = tuple(int(b) for b in OFF[:n_stages + 1])
    args = _weak_specs(v5e, 0, rel[-1]) + [
        _spec(v5e, (n_stages,), jnp.float32), _spec(v5e, (H, W), jnp.float32)]
    compiled = _compile(
        lambda a, b, c, d, e, th, img: fused_head_kernel(
            a, b, c, d, e, th, rel, img, interpret=False), args)
    # the resident SAT fits VMEM without spilling to HBM
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_masked_fused_head_compiles_for_v5e(v5e):
    """The stream step's head: the whole cascade at level 0, each tile
    starting from its windows of the changed-window map (a (ty, tx) int32
    block operand beside the 1/sigma grid)."""
    n_stages = CASC.n_stages
    rel = tuple(int(b) for b in OFF[:n_stages + 1])
    args = _weak_specs(v5e, 0, rel[-1]) + [
        _spec(v5e, (n_stages,), jnp.float32), _spec(v5e, (H, W), jnp.float32),
        _spec(v5e, (NY, NX), jnp.bool_)]
    compiled = _compile(
        lambda a, b, c, d, e, th, img, live: fused_head_kernel(
            a, b, c, d, e, th, rel, img, interpret=False, live=live), args)
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_haar_stage_compiles_for_v5e(v5e):
    s = DENSE_PREFIX
    ty, tx = DEFAULT_TILE
    ny_pad, nx_pad = NY + (-NY) % ty, NX + (-NX) % tx
    args = _weak_specs(v5e, int(OFF[s]), int(OFF[s + 1])) + [
        _spec(v5e, sat_pad_shape(ny_pad, nx_pad), jnp.int32),
        _spec(v5e, (ny_pad, nx_pad), jnp.float32)]
    _compile(lambda a, b, c, d, e, ii, inv: haar_stage_sums_kernel(
        a, b, c, d, e, ii, inv, interpret=False), args)


def test_packed_window_refused_for_tpu_with_named_error(v5e):
    s0, s1 = TAIL_RUN
    k0 = int(OFF[s0])
    rel = tuple(int(b) - k0 for b in OFF[s0:s1 + 1])
    rows = 2 * DEFAULT_TILE[0]
    args = _weak_specs(v5e, k0, int(OFF[s1])) + [
        _spec(v5e, (1, (H + 1) * (W + 1)), jnp.int32)] + [
        _spec(v5e, (rows, DEFAULT_TILE[1]), jnp.int32)] * 4 + [
        _spec(v5e, (rows, DEFAULT_TILE[1]), jnp.float32)]
    # the TPU path raises the named refusal instead of running anything
    with pytest.raises(NotImplementedError, match="does not compile for TPU"):
        packed_stage_sums_kernel(*args[:5], rel, *args[5:], interpret=False)
    assert "Can only load scalars from SMEM" in MOSAIC_REFUSAL
    # ...and Mosaic still refuses the kernel itself, as that error says
    with pytest.raises(Exception, match="Can only load scalars from SMEM"):
        jax.jit(lambda *a: packed_window._packed_call(
            *a[:5], rel, *a[5:], tile=DEFAULT_TILE, interpret=False)
        ).lower(*args).compile()
