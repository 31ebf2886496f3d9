"""Profiler traces: recording a window, and reducing it to metrics.

``Tracer`` records the measured window with JAX's profiler.  ``extract``
turns the recorded ``.xplane.pb`` into plain event rows: every event of the
devices' op lines, and the benchmark's own host spans (``TraceAnnotation``s
named ``bench.*``).  The reductions work on those rows only, so a small
recorded trace checked in as JSON (``bench/tests/data``) pins them down.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"


class Tracer:
    """Records one window with the profiler into a private temporary
    directory, removed by ``close``."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> list[dict]:
        import jax

        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        return extract(paths[0])

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def extract(path: str) -> list[dict]:
    """Device op events and benchmark spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                rows.append({"plane": plane.name, "line": line.name,
                             "name": ev.name, "start_ns": int(ev.start_ns),
                             "dur_ns": int(ev.duration_ns)})
    return rows


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merged (start, end) intervals, in order."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def device_ops(rows: list[dict]) -> list[dict]:
    return [r for r in rows if r["plane"].startswith("/device:")]


def spans(rows: list[dict]) -> list[dict]:
    return [r for r in rows if r["name"].startswith(SPAN_PREFIX)]


def busy(rows: list[dict], t0: int, t1: int) -> tuple[float, list]:
    """Seconds in [t0, t1) in which an op ran, averaged over the devices,
    and each device's merged busy intervals."""
    per_dev: dict[str, list] = defaultdict(list)
    for r in device_ops(rows):
        s = max(r["start_ns"], t0)
        e = min(r["start_ns"] + r["dur_ns"], t1)
        if e > s:
            per_dev[r["plane"]].append((s, e))
    merged = {d: union(iv) for d, iv in per_dev.items()}
    if not merged:
        return 0.0, {}
    total = sum(sum(e - s for s, e in iv) for iv in merged.values())
    return total / len(merged) / 1e9, merged


def short(name: str) -> str:
    """An op's name as the trace gives it (its HLO text) cut to the
    instruction, its output shape and, for a kernel, its target."""
    head = re.sub(r"\{[^}]*\}", "", name).split("(")[0].strip()
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return head + (f" {target.group(1)}" if target else "")


def top_ops(rows: list[dict], n: int = 10) -> list[list]:
    """The ``n`` device ops with the most time, by short name:
    [[name, s], ...]."""
    by: dict[str, int] = defaultdict(int)
    for r in device_ops(rows):
        by[short(r["name"])] += r["dur_ns"]
    return [[k, v / 1e9] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(rows: list[dict], t0: int, t1: int, n: int = 10) -> list[list]:
    """The ``n`` longest stretches of [t0, t1) in which no device ran an
    op, each named by what the host was doing at its middle: the innermost
    benchmark span open on each host thread, joined by "+" ("no span"
    where none was): [[name, s], ...]."""
    _, merged = busy(rows, t0, t1)
    iv = union([x for ivs in merged.values() for x in ivs])
    edges = [t0] + [x for s, e in iv for x in (s, e)] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    sp = [r for r in spans(rows) if r["name"] != "bench.window"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        inner: dict[tuple, dict] = {}
        for r in sp:
            if r["start_ns"] <= mid < r["start_ns"] + r["dur_ns"]:
                key = (r["plane"], r["line"])
                if key not in inner or r["dur_ns"] < inner[key]["dur_ns"]:
                    inner[key] = r
        name = "+".join(sorted({r["name"] for r in inner.values()}))
        out.append([name or "no span", (e - s) / 1e9])
    return out


def to_ns(ctx: dict, t_host: float) -> int:
    """A host ``perf_counter`` time on the trace's clock, by the start of
    the ``bench.window`` span, which began at ``ctx["t0_host"]``."""
    return ctx["t0_ns"] + int((t_host - ctx["t0_host"]) * 1e9)


def in_window(rows: list[dict], t0: int, t1: int) -> list[dict]:
    """Device op events that start in [t0, t1)."""
    return [r for r in device_ops(rows) if t0 <= r["start_ns"] < t1]
