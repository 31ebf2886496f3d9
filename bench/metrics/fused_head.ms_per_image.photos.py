"""Device milliseconds of the fused dense-head kernel per photo: the events
of the kernel in the batches counted by ``images_per_s``, over their
photos."""

from bench import counts, trace


def read(ctx: dict):
    counted = ctx.get("counted")
    if not ctx.get("trace_rows") or not counted:
        return None
    rows = trace.in_window(ctx["trace_rows"], trace.to_ns(ctx, counted[0][0]),
                           trace.to_ns(ctx, counted[-1][1]))
    ns = [r["dur_ns"] for r in rows
          if counts.fused_head_call(r["name"], ctx["arrays"])]
    if not ns:
        return None
    return sum(ns) / 1e6 / sum(c[2] for c in counted)
