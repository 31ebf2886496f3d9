"""Share of the completed frames that ran a full detect (``FrameStats.mode``
"full": keyframes and refreshes)."""


def read(ctx: dict):
    stats = ctx.get("frame_stats")
    if not stats:
        return None
    return 100 * sum(s.mode == "full" for s in stats) / len(stats)
