"""Share of the traced window in which no operation ran on the device while
the service was flushing (a ``serve.flush`` span of the program open):
the device waiting on the flush's own host work.  The rest of
``device.idle_share.photos`` is the device waiting for a flush."""

from bench import trace


def read(ctx: dict):
    try:
        from repro import obs
    except ImportError:
        return None
    rows = ctx.get("trace_rows")
    if not rows:
        return None
    t0, t1 = ctx["t0_ns"], ctx["t1_ns"]
    flushes = trace.union([
        (max(trace.to_ns(ctx, s.t0_ns / 1e9), t0),
         min(trace.to_ns(ctx, s.t1_ns / 1e9), t1))
        for s in obs.spans() if s.name == "serve.flush"])
    flushes = [(s, e) for s, e in flushes if e > s]
    if not flushes:
        return None
    _, merged = trace.busy(rows, t0, t1)
    busy = trace.union([x for iv in merged.values() for x in iv])
    idle = sum(e - s for s, e in flushes)
    for fs, fe in flushes:
        for bs, be in busy:
            idle -= max(min(fe, be) - max(fs, bs), 0)
    return 100 * idle / (t1 - t0)
