"""Sweep the aggregate frame rate of a stream cell, on the chip, to find its
knee: the highest rate the service sustains.

    python3 -m bench.tools.knee --config ff25-trained-vga --traffic cctv-8cam \
        --rates 4 8 12 16 24 --seconds 20

One set-up (``bench.streams.Cameras``), then one window per rate, in
increasing order, each printed with its latency percentiles and the median
latency of its first and last quarter: a backlog that grows through the
window shows as a last quarter far above the first, and ends the sweep.
"""

from __future__ import annotations

import argparse
import time

from bench import harness, streams
from bench.cascade import cascade_arrays, load_config
from bench.run import compile_cache


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seed", type=int, default=2_718_281_828)
    args = ap.parse_args()
    t = time.perf_counter()
    harness.device_info(1)
    compile_cache()
    cfg = load_config(args.config)
    traffic = harness.load_json(harness.BENCH_DIR, "traffic",
                                f"{args.traffic}.json")
    cams = streams.Cameras(cfg, cascade_arrays(cfg), traffic, args.seed)
    print(f"set-up {time.perf_counter() - t:.1f} s", flush=True)
    for rate in args.rates:
        builds = cams.svc._program_build_count()
        cams.svc.start()
        sent = cams.window(rate, args.seconds, False)
        cams.svc.stop()
        _p95, note = streams.summary(sent, rate, args.seconds, len(cams.cams))
        print(f"rate {rate}: {note}; program builds "
              f"{cams.svc._program_build_count() - builds}", flush=True)
        done = [r.t_done - due for r, _c, _j, due in sent if r.done.is_set()]
        q = max(len(done) // 4, 1)
        if len(done) < len(sent) or (
                sorted(done[-q:])[q // 2] > 4 * sorted(done[:q])[q // 2] + 1):
            print(f"rate {rate}: the backlog grew; the sweep stops here")
            break


if __name__ == "__main__":
    main()
