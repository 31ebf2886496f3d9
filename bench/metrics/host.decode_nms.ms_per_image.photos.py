"""Host milliseconds per photo spent decoding survivors into rects and
grouping them: the self time (less any span recorded inside) of the
program's ``engine.decode`` and ``nms.group`` spans, over the batches
counted by ``images_per_s``."""

NAMES = ("engine.decode", "nms.group")


def read(ctx: dict):
    try:
        from repro import obs
    except ImportError:
        return None
    counted = ctx.get("counted")
    if not counted:
        return None
    lo, hi = counted[0][0] * 1e9, counted[-1][1] * 1e9
    ring = obs.spans()
    mine = {s.id: s.t1_ns - s.t0_ns for s in ring
            if s.name in NAMES and lo <= s.t0_ns < hi}
    if not mine:
        return None
    for s in ring:
        if s.parent in mine:
            mine[s.parent] -= s.t1_ns - s.t0_ns
    return sum(mine.values()) / 1e6 / sum(c[2] for c in counted)
