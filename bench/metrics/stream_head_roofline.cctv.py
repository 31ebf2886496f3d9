"""Share of its roofline that the stream step's masked head reaches between
the first and the last counted completion: the least time the chip could
take for the head's operations — counted from the tiles it ran, stage by
stage, off the ``head_windows`` of the program's ``stream.poll`` spans
(``bench.stream_counts.head_ops``) — and for its calls' bytes (from the
shapes the trace names), over the calls' device time."""

from bench import stream_counts, trace


def read(ctx: dict):
    polls = stream_counts.poll_spans(ctx)
    counted = ctx.get("frames_counted")
    if not polls or not ctx.get("trace_rows"):
        return None
    arrays = ctx["arrays"]
    n_stages = len(arrays["stage_threshold"])
    rows = trace.in_window(ctx["trace_rows"], trace.to_ns(ctx, counted[0]),
                           trace.to_ns(ctx, counted[1]))
    nbytes = ns = 0
    for r in rows:
        b = stream_counts.head_call_bytes(r["name"], n_stages)
        if b is not None:
            nbytes, ns = nbytes + b, ns + r["dur_ns"]
    if ns == 0:
        return None
    ops = sum(stream_counts.head_ops(s.attrs["head_windows"], arrays)
              for s in polls)
    peak = ctx["peak"]
    least = max(ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100 * least / (ns / 1e9)
