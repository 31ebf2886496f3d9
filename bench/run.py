"""Run one cell of ``BENCHMARK.json`` on the chip this process is started on.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); the mix's ``kind`` picks the
driver (``bench/<kind>.py``), and each per-layer metric is read by
``bench/metrics/<metric>.py``.  With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the window is recorded by the
profiler and the result carries the cell's per-layer metrics, the device's
busy time and a breakdown.  Every run checks what the timed path produced
against the plain reference (``bench/reference.py``): the compared numbers
and their limits are the last lines on stderr and the ``checks`` of the
result, which is the last line on stdout.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

if __package__ in (None, ""):       # run as a file: make ``bench`` importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import harness, trace  # noqa: E402
from bench.cascade import cascade_arrays, load_config  # noqa: E402


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The cell's end-to-end (``kind`` "end_to_end") or per-layer metrics."""
    reported = {m["name"] for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])}
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])
            and (kind == "end_to_end" or m["moves"] in reported)]


def compile_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed path in
    the checkout (``repro.compile_cache``), for every program, so that only
    a checkout's first run of a cell compiles."""
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    import jax
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"bench: no cell {args.workload!r}; cells are "
                         f"{sorted(cells)}")
    cell = cells[args.workload]
    cfg = load_config(cell["config"])
    traffic = harness.load_json(harness.BENCH_DIR, "traffic",
                                f"{cell['traffic']}.json")
    device = harness.device_info(cell["chips"])
    compile_cache()
    sys.stdout.reconfigure(line_buffering=True)

    from bench.peaks import peak

    driver = importlib.import_module(f"bench.{traffic['kind']}")
    tracer = trace.Tracer() if args.trace else None
    try:
        out = driver.run(cfg, cascade_arrays(cfg), traffic, args.seed,
                         args.seconds, tracer, T_PROCESS)
    finally:
        if tracer is not None:
            tracer.close()
    device["memory_peak_bytes"] = out.memory_peak_bytes
    breakdown = None
    if args.trace:
        rows = out.ctx["trace_rows"]
        win = [r for r in trace.spans(rows) if r["name"] == "bench.window"]
        t0 = win[0]["start_ns"]
        t1 = t0 + win[0]["dur_ns"]
        busy_s, _ = trace.busy(rows, t0, t1)
        device["busy_s"] = busy_s
        device["window_s"] = (t1 - t0) / 1e9
        out.ctx.update(t0_ns=t0, t1_ns=t1, peak=peak(device["kind"]),
                       busy_s=busy_s, window_s=(t1 - t0) / 1e9)
        breakdown = {"device_ops": trace.top_ops(rows),
                     "idle_gaps": trace.idle_gaps(rows, t0, t1)}
        metrics = {}
        for m in cell_metrics(bench, args.workload, "per_layer"):
            v = harness.read_metric(m["name"], out.ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(out.e2e, setup_s=out.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(bench, args.workload, "end_to_end")}
    harness.emit(out, metrics, device, breakdown)


if __name__ == "__main__":
    main()
