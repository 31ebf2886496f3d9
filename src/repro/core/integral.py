"""Integral images (Viola-Jones Eq. 3) — pure-jnp reference layer.

Conventions
-----------
``integral_image`` returns the *padded* summed-area table of shape (H+1, W+1)
with a zero top row / left column, so that the sum of pixels inside the
half-open rectangle ``[y0, y0+h) x [x0, x0+w)`` is::

    ii[y0+h, x0+w] - ii[y0, x0+w] - ii[y0+h, x0] + ii[y0, x0]

(4 memory accesses — Fig. 4 of the paper).

dtype: int32, wrapping.  Pixels are whole numbers (uint8 imagery; other
values are rounded), so every table is an exact integer prefix sum taken
modulo 2^32: ``jnp.cumsum`` in int32 wraps past 2^31 on purpose (a VGA
squared table reaches ~5e9).  Every rectangle sum of a 24x24 window fits
in int32 (at most 576 * 255 for the plain table, 576 * 128^2 for the
centred squared one), and two's-complement differences are exact modulo
2^32, so the four-corner difference of a wrapped table is the exact sum at
any image size.  Readers take the corner differences in int32 and convert
to float32 only afterwards (:func:`rect_sum`, :func:`grid_rect_sum`, the
kernels' ``weak_vote``); normalization, votes and stage sums stay float32.
A float32 table would round once its entries pass 2^24 (255 * 640 * 480
~ 7.8e7 at VGA), and the rounding of a window's corners would depend on
every pixel above and left of it.

Window-locality.  With exact integer tables, a window's rectangle sums,
its 1/sigma and so its stage sums are a pure function of the pixels under
the window: identical pixels give bit-identical decisions in any frame,
batch, bucket or padding context.  The streaming engine
(:mod:`repro.stream`) relies on this to reuse cached per-window decisions
for unchanged tiles across frames.  The centring constant of the squared
and centred tables is *fixed* (``CENTRE = 128``, mid-range of uint8
imagery) rather than the per-image mean, for the same reason: a
content-dependent centre would couple every window's normalization to
every pixel of the image.  Centring also bounds the squared table's
window sums (|x - 128| <= 128), which keeps them exact in float32 (below
2^24) once differenced.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "integral_image",
    "integral_images",
    "rect_sum",
    "grid_rect_sum",
    "window_inv_sigma",
    "window_inv_sigma_grid",
    "integral_value",
    "pixels",
    "CENTRE",
]

# fixed centring constant of the squared/centred SATs (see module docstring):
# content-independent so window normalization is window-local, which is what
# lets repro.stream reuse cached per-window results across video frames.
CENTRE = 128


def pixels(img: jax.Array) -> jax.Array:
    """The image as int32 whole numbers, the tables' input."""
    if jnp.issubdtype(img.dtype, jnp.integer):
        return img.astype(jnp.int32)
    return jnp.round(img).astype(jnp.int32)


def integral_image(img: jax.Array) -> jax.Array:
    """Padded summed-area table, shape (H+1, W+1), int32 (wrapping)."""
    ii = jnp.cumsum(jnp.cumsum(pixels(img), axis=0), axis=1)
    return jnp.pad(ii, ((1, 0), (1, 0)))


def integral_images(img: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(integral, squared-integral) of a grayscale image.

    The squared integral is computed over the *centred* image (fixed
    ``CENTRE`` shift, see module docstring); the constant shift cancels in
    the variance identity used by :func:`window_inv_sigma`.  All three
    tables are int32 and wrap.
    """
    img = pixels(img)
    centred = img - CENTRE
    ii = integral_image(img)
    ii2 = integral_image(centred * centred)
    # Also need the centred first-moment table to reconstruct the window
    # variance exactly:  var = E[(x-mu)^2] - (E[x-mu])^2.
    iic = integral_image(centred)
    return ii, jnp.stack([ii2, iic])


def rect_sum(ii: jax.Array, ys: jax.Array, xs: jax.Array,
             h: jax.Array, w: jax.Array) -> jax.Array:
    """Sum of pixels in ``[ys, ys+h) x [xs, xs+w)`` — broadcasts over ys/xs.
    The corners are differenced in the table's dtype (int32: exact under
    wrap-around), then converted to float32."""
    y1 = ys + h
    x1 = xs + w
    return (ii[y1, x1] - ii[ys, x1] - ii[y1, xs] + ii[ys, xs]
            ).astype(jnp.float32)


def grid_rect_sum(ii: jax.Array, ny: int, nx: int, step: int,
                  y: jax.Array, x: jax.Array, h: jax.Array,
                  w: jax.Array) -> jax.Array:
    """:func:`rect_sum` over the whole (ny, nx) grid of window origins
    ``(step * i, step * j)``, offset by ``(y, x)``: each corner is one
    (strided) slice of the SAT instead of a gather — the same elements in
    the same float ordering, so the result is bit-identical, but a dense
    grid costs slices, which TPUs stream, instead of gathers, which they
    serialize.  ``y``/``x``/``h``/``w`` may be traced scalars."""
    span = ((ny - 1) * step + 1, (nx - 1) * step + 1)

    def corner(cy, cx):
        return jax.lax.dynamic_slice(ii, (cy, cx), span)[::step, ::step]

    return (corner(y + h, x + w) - corner(y, x + w) - corner(y + h, x)
            + corner(y, x)).astype(jnp.float32)


def _inv_sigma(s2: jax.Array, s1: jax.Array, window: int) -> jax.Array:
    n = float(window * window)
    var = s2 / n - (s1 / n) ** 2
    sigma = jnp.sqrt(jnp.maximum(var, 1.0))
    return 1.0 / sigma


def window_inv_sigma(ii_pair: jax.Array, ys: jax.Array, xs: jax.Array,
                     window: int) -> jax.Array:
    """1 / sigma for each detection window (paper Eq. 5, float-safe form).

    ``ii_pair`` is the stacked (ii2, iic) pair returned by
    :func:`integral_images`.  sigma is the per-pixel standard deviation of
    the window, clamped to >= 1 so flat windows do not blow up the
    normalized feature values (same guard as the reference C code's
    ``int_sqrt`` path).
    """
    ii2, iic = ii_pair[0], ii_pair[1]
    s2 = rect_sum(ii2, ys, xs, window, window)      # sum (x-mu)^2
    s1 = rect_sum(iic, ys, xs, window, window)      # sum (x-mu)
    return _inv_sigma(s2, s1, window)


def window_inv_sigma_grid(ii_pair: jax.Array, ny: int, nx: int, step: int,
                          window: int) -> jax.Array:
    """(ny, nx) :func:`window_inv_sigma` over the grid of window origins
    ``(step * i, step * j)``, from slices (:func:`grid_rect_sum`);
    bit-identical to the gather form."""
    ii2, iic = ii_pair[0], ii_pair[1]
    s2 = grid_rect_sum(ii2, ny, nx, step, 0, 0, window, window)
    s1 = grid_rect_sum(iic, ny, nx, step, 0, 0, window, window)
    return _inv_sigma(s2, s1, window)


def integral_value(img: jax.Array) -> jax.Array:
    """The paper's 'integral value' — the bottom-right entry of the SAT,
    i.e. the sum of every pixel in the image (used by the RIT relation,
    Eq. 6)."""
    return jnp.sum(img.astype(jnp.float32))
