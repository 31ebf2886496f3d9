"""Share of the windows of the completed frames whose cached decision the
stream reused: 1 - sum(windows_recomputed) / sum(windows_total) over the
frames' ``FrameStats``."""


def read(ctx: dict):
    stats = ctx.get("frame_stats")
    if not stats:
        return None
    total = sum(s.windows_total for s in stats)
    return 100 * (1 - sum(s.windows_recomputed for s in stats) / total)
