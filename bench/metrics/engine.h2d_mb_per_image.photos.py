"""Megabytes (1e6 B) put on the device per photo: the ``bytes`` of the
program's ``engine.pack`` spans (the padded stack and its true shapes),
over the batches counted by ``images_per_s``."""


def read(ctx: dict):
    try:
        from repro import obs
    except ImportError:
        return None
    counted = ctx.get("counted")
    if not counted:
        return None
    lo, hi = counted[0][0] * 1e9, counted[-1][1] * 1e9
    nbytes = [s.attrs["bytes"] for s in obs.spans()
              if s.name == "engine.pack" and "bytes" in s.attrs
              and lo <= s.t0_ns < hi]
    if not nbytes:
        return None
    return sum(nbytes) / 1e6 / sum(c[2] for c in counted)
