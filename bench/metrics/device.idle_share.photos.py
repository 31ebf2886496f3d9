"""Share of the traced window in which no operation ran on the device."""


def read(ctx: dict):
    if not ctx.get("trace_rows") or ctx["window_s"] <= 0:
        return None
    return 100 * (1 - ctx["busy_s"] / ctx["window_s"])
