"""What every cell's run shares: the device check, spans, the outcome of a
run, the per-layer metric readers, and the result line."""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Outcome:
    """What a driver hands back: end-to-end metrics, the checks that decide
    ``correct`` (name -> (value, limit); a value above its limit fails),
    notes printed before the result, and what the per-layer readers read."""
    setup_s: float
    attempted: int = 0
    failed: int = 0
    e2e: dict = field(default_factory=dict)
    checks: dict = field(default_factory=dict)
    notes: list = field(default_factory=list)
    ctx: dict = field(default_factory=dict)
    memory_peak_bytes: int = 0

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            v <= lim for v, lim in self.checks.values())


def span(name: str, on: bool):
    """A profiler span named ``name`` when ``on``, else nothing."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def device_info(chips: int) -> dict:
    """The accelerator JAX sees; exits non-zero, printing no result, unless
    it is a TPU with at least ``chips`` chips."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or len(devs) < chips:
        sys.stderr.write(
            f"bench: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} x {d.device_kind} ({d.platform})\n")
        raise SystemExit(2)
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def read_metric(name: str, ctx: dict):
    """Run the reader ``bench/metrics/<name>.py``; None where it found
    nothing to read."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def checks_line(checks: dict) -> dict:
    return {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}


def emit(outcome: Outcome, metrics: dict, device: dict,
         breakdown: dict | None) -> None:
    """Notes and the result on stdout, the compared numbers last on
    stderr."""
    for note in outcome.notes:
        print(note)
    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks_line(outcome.checks)
    for k, (v, lim) in outcome.checks.items():
        sys.stderr.write(f"check {k}: {v!r} (limit {lim!r})\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
