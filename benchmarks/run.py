"""Benchmark harness: one module per paper table/figure (+ TPU extras).

    python -m benchmarks.run [--fast] [--only bench_rit,bench_dvfs]
                             [--artifacts DIR]

``--artifacts DIR`` additionally writes one machine-readable
``BENCH_<name>.json`` per benchmark that returned rows — CI points it at
the repo root so the perf trajectory is tracked across PRs.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import time
import traceback

from repro.compile_cache import use_compile_cache

BENCHES = [
    ("bench_kernels", "Pallas kernels vs oracle (shape sweep)"),
    ("bench_profile", "Fig 13  — per-phase cost profile"),
    ("bench_rit", "Figs 10–12 — time vs content, RIT relation"),
    ("bench_speedup", "Fig 16  — seq vs parallel, both boards"),
    ("bench_energy", "Figs 17–18 — modeled energy + serving governor Pareto"),
    ("bench_param_sweep", "Fig 20  — error vs step/scaleFactor"),
    ("bench_dvfs", "Figs 21–24 + Table I — DVFS grid + optimum"),
    ("bench_detector", "Tables II/III — ours vs dense reference"),
    ("bench_serving", "batched detection serving: throughput + latency"),
    ("bench_video", "streaming video: tile-reuse vs per-frame detection"),
    ("bench_fleet", "fleet-scale multi-tenant streams: tiers + admission"),
    ("bench_roofline", "roofline table from dry-run artifacts"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--artifacts", default=None, metavar="DIR",
                    help="write BENCH_<name>.json per benchmark into DIR")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    print(f"compile cache: {use_compile_cache()}")

    failures = []
    for name, desc in BENCHES:
        if only and name not in only:
            continue
        print(f"\n=== {name}: {desc} ===", flush=True)
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            rows = mod.main(fast=args.fast)
            print(f"[{name} done in {time.time() - t0:.1f}s]")
            if args.artifacts and rows is not None:
                _write_artifact(args.artifacts, name, args.fast, rows)
        except Exception:                                # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
    print("\n" + ("ALL BENCHMARKS PASSED" if not failures else
                  f"FAILURES: {failures}"))
    if failures:
        raise SystemExit(1)


def _write_artifact(out_dir: str, name: str, fast: bool, rows) -> None:
    short = name.removeprefix("bench_")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{short}.json")
    with open(path, "w") as f:
        json.dump({"bench": name, "fast": fast,
                   "written_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                               time.gmtime()),
                   "rows": rows}, f, indent=1, default=float)
    print(f"[artifact: {path}]")


if __name__ == "__main__":
    main()
