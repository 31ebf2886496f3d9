"""Share of the dense head's weak-classifier work that the fused head ran:
the ``head_work`` of the program's ``engine.fetch`` spans (evaluations of
(ty, tx) tiles, stage by stage, that the early exit did not skip) over
their ``head_dense`` (every tile through every dense stage), in the batches
counted by ``images_per_s``."""


def read(ctx: dict):
    try:
        from repro import obs
    except ImportError:
        return None
    counted = ctx.get("counted")
    if not counted:
        return None
    lo, hi = counted[0][0] * 1e9, counted[-1][1] * 1e9
    pairs = [(s.attrs["head_work"], s.attrs["head_dense"])
             for s in obs.spans()
             if s.name == "engine.fetch" and "head_dense" in s.attrs
             and lo <= s.t0_ns < hi]
    dense = sum(d for _, d in pairs)
    if not dense:
        return None
    return 100 * sum(w for w, _ in pairs) / dense
