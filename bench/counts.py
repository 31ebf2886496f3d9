"""Operations and bytes of the cascade's work, counted from shapes.

A weak classifier over one window costs, per rectangle, 3 additions to
combine the four corners of the summed-area table, 1 multiplication by the
rectangle's weight and 1 addition into the feature; then 2 multiplications
(by 1/sigma and by 1/area), 1 comparison with the stump threshold, 1 select
of the vote and 1 addition into the stage sum.  So a two-rectangle feature
costs 15 operations and a three-rectangle one 20.

The fused dense head evaluates every weak classifier of its stages on every
window origin of its grid; its operations and bytes are counted from the
shapes of each call as the device trace names it.  The work an early-exit
cascade needs is that of the windows entering each stage only: the useful
work.
"""

from __future__ import annotations

import re

import numpy as np

PER_RECT = 5
PER_WEAK = 5


def weak_ops(rect_w: np.ndarray) -> np.ndarray:
    """Operations of each weak classifier on one window."""
    n_rects = (np.asarray(rect_w) != 0).sum(axis=1)
    return PER_RECT * n_rects + PER_WEAK


def stage_ops(rect_w: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """Operations of each whole stage on one window."""
    ops = np.concatenate([[0], np.cumsum(weak_ops(rect_w))])
    off = np.asarray(offsets)
    return ops[off[1:]] - ops[off[:-1]]


def useful_ops(entering: np.ndarray, arrays: dict) -> int:
    """Operations an early-exit cascade needs for one frame, given the
    windows entering each stage."""
    return int(np.dot(np.asarray(entering, np.int64),
                      stage_ops(arrays["rect_w"], arrays["stage_offsets"])))


def entering(alive_after: np.ndarray, n_windows: int) -> np.ndarray:
    """Windows entering each stage from the windows alive after each."""
    a = np.asarray(alive_after, np.int64)
    return np.concatenate([[n_windows], a[:-1]])


_DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
                "u8": 1, "pred": 1}
_SHAPE = r"(\w+)\[([\d,]*)\]"


def _nbytes(dtype: str, dims: str) -> int:
    return _DTYPE_BYTES[dtype] * int(np.prod([int(d) for d in dims.split(",")
                                              if d] or [1]))


def call_shapes(hlo: str) -> tuple[tuple[str, list[int]], int]:
    """The output (dtype, dims) of one op as the device trace names it
    (``%name = f32[8,25,464,640]{...} custom-call(f32[...]{...} %a, ...)``)
    and the bytes of its output and operands."""
    out = re.match(r"%\S+ = " + _SHAPE, hlo)
    if out is None:
        raise ValueError(f"no output shape in {hlo[:80]!r}")
    body = hlo[out.end():]
    operands = re.findall(_SHAPE + r"\{[^}]*\} %", body)
    nbytes = _nbytes(*out.groups()) + sum(_nbytes(*o) for o in operands)
    return (out.group(1), [int(d) for d in out.group(2).split(",") if d]), \
        nbytes


def fused_head_call(hlo: str, arrays: dict) -> tuple[int, int] | None:
    """Operations and bytes that one fused dense-head call asks for, from
    the shapes the device trace names: its output is the stage sums,
    (batch, stages, rows, columns) of window origins; every weak classifier
    of those stages runs on every origin.  None for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return None
    (dtype, dims), nbytes = call_shapes(hlo)
    if dtype != "f32" or len(dims) != 4:
        return None
    batch, n_stages, ny, nx = dims
    per_window = int(stage_ops(arrays["rect_w"],
                               arrays["stage_offsets"])[:n_stages].sum())
    return batch * ny * nx * per_window, nbytes
