"""Share of its roofline that the fused dense head reaches in the batches
counted by ``images_per_s``: the least time the chip could take for the
operations and bytes its calls ask for, each counted from the call's own
shapes (``bench.counts.fused_head_call``), over the calls' device time.  The
larger of the two bounds applies; at these shapes it is the operations."""

from bench import counts, trace


def read(ctx: dict):
    counted = ctx.get("counted")
    if not ctx.get("trace_rows") or not counted:
        return None
    rows = trace.in_window(ctx["trace_rows"], trace.to_ns(ctx, counted[0][0]),
                           trace.to_ns(ctx, counted[-1][1]))
    ops = nbytes = ns = 0
    for r in rows:
        call = counts.fused_head_call(r["name"], ctx["arrays"])
        if call:
            ops, nbytes, ns = ops + call[0], nbytes + call[1], ns + r["dur_ns"]
    if ns == 0:
        return None
    peak = ctx["peak"]
    least = max(ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100 * least / (ns / 1e9)
