"""Mean milliseconds a photo request waited from its submission to the
dispatch of the chunk that carries it (the program's ``serve.queue``
intervals), over the requests whose wait ended in the batches counted by
``images_per_s``.  The mean, since a backlog's requests wait in two modes:
those of a flush's first chunk, and those queued a chunk behind them."""


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
          if s.name == "serve.queue" and lo <= s.t1_ns < hi]
    if not ns:
        return None
    return sum(ns) / len(ns) / 1e6
