"""Share of the chip's peak that useful cascade work reaches over the traced
window: each photo completed in the window counts the weak-classifier
operations an early-exit cascade needs for it (windows entering each stage,
from the per-stage alive counts the check compares, times the stage's
operations per window), over the window and the peak."""

from bench import trace


def read(ctx: dict):
    if not ctx.get("trace_rows") or not ctx.get("served"):
        return None
    ops = sum(u for t, u in ctx["served"]
              if ctx["t0_ns"] <= trace.to_ns(ctx, t) < ctx["t1_ns"])
    if ops == 0:
        return None
    return 100 * ops / ctx["window_s"] / ctx["peak"]["flops_per_s"]
