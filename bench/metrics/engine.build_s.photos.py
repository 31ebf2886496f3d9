"""Seconds of set-up in which JAX traced, lowered or compiled (or loaded
from its persistent cache) a program: the union of the ``jax.trace``,
``jax.lower`` and ``jax.compile`` intervals that ended before the window
opened.  The union, since a nested jit reports events inside its
caller's."""

NAMES = ("jax.trace", "jax.lower", "jax.compile")


def read(ctx: dict):
    try:
        from repro import obs
    except ImportError:
        return None
    if "t0_host" not in ctx:
        return None
    end = ctx["t0_host"] * 1e9
    iv = sorted((s.t0_ns, s.t1_ns) for s in obs.spans()
                if s.name in NAMES and s.t1_ns < end)
    if not iv:
        return None
    total, reach = 0, iv[0][0]
    for s, e in iv:
        total += max(e - max(s, reach), 0)
        reach = max(reach, e)
    return total / 1e9
