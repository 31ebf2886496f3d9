"""Device milliseconds per completed frame: the time in which a device op
ran (the union of the ops' intervals, so an op nested in a conditional
counts once) between the first and the last counted completion, over the
frames completed in that span.  The cell runs no program but the stream
step, so these are the ops under the program's ``stream.step``
dispatches."""

from bench import trace


def read(ctx: dict):
    counted = ctx.get("frames_counted")
    if not ctx.get("trace_rows") or not counted or not counted[2]:
        return None
    busy_s, merged = trace.busy(ctx["trace_rows"],
                                trace.to_ns(ctx, counted[0]),
                                trace.to_ns(ctx, counted[1]))
    if not merged:
        return None
    return busy_s * 1e3 / counted[2]
