"""Host milliseconds per completed frame spent after the device: the self
time (less any span recorded inside) of the program's ``stream.poll``
(copying the verdict), ``stream.fetch`` (copying the survivor slots),
``stream.decode`` and ``nms.group`` spans that start between the first and
the last counted completion, over the frames completed in that span."""

NAMES = ("stream.poll", "stream.fetch", "stream.decode", "nms.group")


def read(ctx: dict):
    try:
        from repro import obs
    except ImportError:
        return None
    counted = ctx.get("frames_counted")
    if not counted or not counted[2]:
        return None
    lo, hi = counted[0] * 1e9, counted[1] * 1e9
    ring = obs.spans()
    if not any(s.name == "stream.poll" for s in ring):
        return None
    mine = {s.id: s.t1_ns - s.t0_ns for s in ring
            if s.name in NAMES and lo <= s.t0_ns < hi}
    if not mine:
        return None
    for s in ring:
        if s.parent in mine:
            mine[s.parent] -= s.t1_ns - s.t0_ns
    return sum(mine.values()) / 1e6 / counted[2]
