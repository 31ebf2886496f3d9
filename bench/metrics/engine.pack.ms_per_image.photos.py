"""Host milliseconds per photo that the engine spent packing a batch,
uploading it and dispatching its program (the program's ``engine.pack``
spans), over the batches counted by ``images_per_s``."""


def read(ctx: dict):
    try:
        from repro import obs
    except ImportError:
        return None
    counted = ctx.get("counted")
    if not counted:
        return None
    lo, hi = counted[0][0] * 1e9, counted[-1][1] * 1e9
    ns = [s.t1_ns - s.t0_ns for s in obs.spans()
          if s.name == "engine.pack" and lo <= s.t0_ns < hi]
    if not ns:
        return None
    return sum(ns) / 1e6 / sum(c[2] for c in counted)
