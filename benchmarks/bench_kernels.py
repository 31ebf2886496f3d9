"""Pallas kernel validation sweep + timing.

Sweeps shapes/dtypes for each TPU kernel against the pure-jnp oracle.
On CPU the kernels run in the Pallas interpreter, so wall numbers time
the interpreter and the oracle path; correctness is the deliverable
there."""

from __future__ import annotations

import numpy as np

from .common import save_rows, print_table, Timer, pretrained_cascade


def run(fast: bool = False) -> list[dict]:
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.core.integral import integral_images

    rng = np.random.default_rng(7)
    casc, _ = pretrained_cascade()
    shapes = [(64, 128), (96, 96), (128, 256)] if not fast \
        else [(64, 128), (96, 96)]
    rows = []
    for (h, w) in shapes:
        img = jnp.asarray(rng.integers(0, 255, (h, w)).astype(np.float32))
        ii_k = ops.integral_image(img, use_kernel=True)
        ii_r = ops.integral_image(img, use_kernel=False)
        err = float(jnp.max(jnp.abs(ii_k - ii_r)))
        with Timer() as t:
            ops.integral_image(img, use_kernel=False).block_until_ready()
        rows.append({"kernel": "integral_image", "shape": f"{h}x{w}",
                     "max_err": err, "ok": err < 1e-3 * h * w,
                     "ref_us": t.seconds * 1e6})

        ii, ii_pair = integral_images(img)
        ny, nx = h - 24 + 1, w - 24 + 1
        inv_k = ops.window_inv_sigma_grid(ii_pair, ny, nx, use_kernel=True)
        inv_r = ops.window_inv_sigma_grid(ii_pair, ny, nx, use_kernel=False)
        err = float(jnp.max(jnp.abs(inv_k - inv_r)))
        rows.append({"kernel": "window_inv_sigma", "shape": f"{ny}x{nx}",
                     "max_err": err, "ok": err < 1e-3,
                     "ref_us": None})

        s_k = ops.dense_stage_sums(casc, casc, 0, ii, inv_r)
        s_r = ops.dense_stage_sums_ref(casc, casc, 0, ii, inv_r)
        err = float(jnp.max(jnp.abs(s_k - s_r)))
        rows.append({"kernel": "haar_stage_sums", "shape": f"{ny}x{nx}",
                     "max_err": err, "ok": err < 1e-2,
                     "ref_us": None})
    rows.extend(_fused_head_rows(casc, rng, fast))
    return rows


def _fused_head_rows(casc, rng, fast: bool) -> list[dict]:
    """Fused Haar-head megakernel vs the split three-dispatch path, per
    pyramid level of a dense workload: bit-exactness under jit (the
    engine's contract — both paths are jitted there) plus the autotuner's
    own split/fused timings and the mode its crossover ladder chose."""
    import jax
    import jax.numpy as jnp
    from repro.core.cascade import WINDOW
    from repro.core.integral import integral_images, window_inv_sigma
    from repro.core.pyramid import pyramid_plan, downscale_indices
    from repro.kernels import ops, ref
    from repro.kernels import autotune as ktune

    h0 = 64 if fast else 96
    base = jnp.asarray(rng.integers(0, 255, (h0, h0)).astype(np.float32))
    workload = []
    for lv in pyramid_plan(h0, h0, 1.3):
        ys = downscale_indices(h0, lv.height)
        xs = downscale_indices(h0, lv.width)
        workload.append((base[ys[:, None], xs[None, :]], 1.0))
    n_dense = min(3, casc.n_stages)
    head = ktune.measure_head(casc, workload, n_dense=n_dense,
                              repeats=1, inner=2 if fast else 3)

    def split_head(c, im):
        ii, pair = integral_images(im)
        h, w = im.shape
        ny, nx = h - WINDOW + 1, w - WINDOW + 1
        inv = window_inv_sigma(pair, jnp.arange(ny)[:, None],
                               jnp.arange(nx)[None, :], WINDOW)
        sums = jnp.stack([ops.dense_stage_sums(c, casc, s, ii, inv)
                          for s in range(n_dense)])
        return ii, inv, sums

    # jitted once; jax retraces per level shape — same cache discipline
    # as the engine, and what the bit-exactness contract is stated over
    split_fn = jax.jit(split_head)
    fused_fn = jax.jit(lambda c, im: ops.fused_head(c, casc, 0, n_dense,
                                                    im))
    rows = []
    for i, (h, w, nwin) in enumerate(head["levels"]):
        img_l = workload[i][0]
        ii, inv, sums = split_fn(casc, img_l)
        # the fused head skips the stages a tile's windows never reach
        want = (ii, inv, ref.tile_exit_ref(
            sums, casc.stage_threshold[:n_dense]))
        got = fused_fn(casc, img_l)
        err = max(float(jnp.max(jnp.abs(jnp.where(g == wn, 0.0, g - wn))))
                  for g, wn in zip(got, want))
        bit = all(bool(jnp.all(g == wn)) for g, wn in zip(got, want))
        s_ms, f_ms = head["ms"]["split"][i], head["ms"]["fused"][i]
        rows.append({"kernel": "fused_head", "shape": f"{h}x{w}",
                     "max_err": err, "ok": bit, "ref_us": s_ms * 1e3,
                     "bit_exact": bit, "split_ms": s_ms, "fused_ms": f_ms,
                     "n_windows": nwin,
                     "mode": "fused" if f_ms <= s_ms else "split"})
    ty, tx = head["head_tiles"]
    rows.append({"kernel": "fused_head_autotune", "shape": f"{ty}x{tx}",
                 "max_err": 0.0, "ok": True, "ref_us": None,
                 "head_tiles": list(head["head_tiles"]),
                 "crossover": head["crossover"],
                 "rungs": [list(r) for r in head["rungs"]]})
    return rows


def main(fast: bool = False):
    rows = run(fast=fast)
    print_table(rows)
    save_rows("bench_kernels", rows)
    assert all(r["ok"] for r in rows), "kernel mismatch vs oracle"
    return rows


if __name__ == "__main__":
    main()
