"""Operations and bytes of the stream step's masked head, and the spans
that count them.

The masked fused head runs each stage of the cascade on every window of
each (8, 128) tile that still has a live window entering the stage; the
program's ``stream.poll`` span of each frame carries ``head_windows``, the
windows of the tiles that ran each stage, summed over the levels the step
evaluated.  So the head's operations are counted from the tiles it ran,
stage by stage, at ``bench.counts.stage_ops`` per window, and not from the
call's shapes, which cover every tile whether it ran or not.  Its bytes
are the call's operands and output, from the shapes the device trace
names.
"""

from __future__ import annotations

import numpy as np

from bench import counts


def head_ops(head_windows, arrays: dict) -> int:
    """Operations of one frame's head: windows of the tiles that ran each
    stage times the stage's operations per window."""
    w = np.asarray(head_windows, np.int64)
    per = counts.stage_ops(arrays["rect_w"], arrays["stage_offsets"])
    return int(np.dot(w, per[:len(w)]))


def head_call_bytes(hlo: str, n_stages: int) -> int | None:
    """Bytes of one masked-head call as the device trace names it: a
    kernel whose output is the (stages, rows, columns) float32 sums of one
    level.  None for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return None
    (dtype, dims), nbytes = counts.call_shapes(hlo)
    if dtype != "f32" or len(dims) != 3 or dims[0] != n_stages:
        return None
    return nbytes


def poll_spans(ctx: dict) -> list | None:
    """The program's ``stream.poll`` spans that carry the head's counters
    and start between the first and the last counted completion; None
    without ``repro.obs`` or a counted window."""
    try:
        from repro import obs
    except ImportError:
        return None
    counted = ctx.get("frames_counted")
    if not counted:
        return None
    lo, hi = counted[0] * 1e9, counted[1] * 1e9
    return [s for s in obs.spans()
            if s.name == "stream.poll" and "head_windows" in s.attrs
            and lo <= s.t0_ns < hi]
