"""Host-side geometry view over a bucket's :class:`CascadePlan`.

:class:`StreamGeometry` is the stream layer's handle on one shape bucket:
the pyramid plan, per-level window grids and the flat slot layout the
stream's survivor slots decode through.  It derives everything from
``compile_plan`` — it computes no geometry of its own — and exists so host
code can read the plan without touching jitted executors.
"""

from __future__ import annotations

from repro.core.pyramid import PyramidLevel

from .compiler import compile_plan

__all__ = ["StreamGeometry"]


class StreamGeometry:
    """Static per-bucket geometry: pyramid plan, per-level window grids and
    flat slot layout, all read off the bucket's compiled
    :class:`CascadePlan`."""

    def __init__(self, detector, hp: int, wp: int):
        base = compile_plan(detector.config, detector.n_stages, hp, wp)
        self.hp, self.wp = hp, wp
        self.step = detector.config.step
        self.plan = [PyramidLevel(lp.height, lp.width, lp.scale)
                     for lp in base.levels]
        self.level_windows = [(lp.ny, lp.nx) for lp in base.levels]
        self.slot_offsets = [0] + [lp.slot_offset + lp.n_windows
                                   for lp in base.levels]
        self.n_slots = base.n_slots
        self.lvl_of_slot = base.layout.lvl_of_slot
        self.y_of_slot = base.layout.y_of_slot
        self.x_of_slot = base.layout.x_of_slot
