"""Share of the chip's peak that the stream step's head work reaches over
the traced window: the operations of the tiles the masked head ran
(``bench.stream_counts.head_ops`` of each ``stream.poll`` span that starts
in the window), over the window and the peak."""

from bench import stream_counts, trace


def read(ctx: dict):
    if not ctx.get("trace_rows") or stream_counts.poll_spans(ctx) is None:
        return None
    from repro import obs

    ops = sum(stream_counts.head_ops(s.attrs["head_windows"], ctx["arrays"])
              for s in obs.spans()
              if s.name == "stream.poll" and "head_windows" in s.attrs
              and ctx["t0_ns"] <= trace.to_ns(ctx, s.t0_ns / 1e9)
              < ctx["t1_ns"])
    if ops == 0:
        return None
    return 100 * ops / ctx["window_s"] / ctx["peak"]["flops_per_s"]
