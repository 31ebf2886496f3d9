"""Readings that set the limit of a stream cell's check, on the chip.

    python3 -m bench.tools.frame_readings --workload <cell> [--frames 64]

Each camera of the cell's mix, frames 0 to ``--frames`` - 1, through a
device-resident stream of the program the window runs (threshold 0, the
mix's keyframe interval), against the plain reference: the lower readings.
The same frames with the control, the reference computed in bfloat16, put
in the program's place: the upper readings.  Both are read as a run's
check reads them (``bench.streams.check``): the raw rects a frame's answer
was grouped from against the reference's survivors, as rectangles.  One
JSON line per camera, then ``frame_survivors_off`` pooled over every frame
and over the samples of ``check_frames`` frames that many seeds draw: the
largest the program gives and the smallest the control gives.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from bench import harness, reference, scenes
from bench.cascade import cascade_arrays, load_config
from bench.photos import detector
from bench.run import compile_cache
from bench.streams import RawRecorder


def cameras(cfg: dict, traffic: dict) -> list:
    h, w = cfg["frame_hw"]
    lo, hi = traffic["faces"]
    out = []
    for c in range(traffic["cameras"]):
        rng = np.random.default_rng([traffic["scene_seed"], c])
        out.append(scenes.ParkedCamera(
            int(rng.integers(2**62)), h, w, int(rng.integers(lo, hi + 1)),
            traffic["object"], traffic["move_px"], traffic["move_every"]))
    return out


def off(have: set, want: set) -> tuple[int, int]:
    return len(have ^ want), len(have | want)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--frames", type=int, default=64)
    ap.add_argument("--seeds", type=int, default=2000)
    args = ap.parse_args()
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = {c["name"]: c for c in bench["workloads"]}[args.workload]
    cfg = load_config(cell["config"])
    traffic = harness.load_json(harness.BENCH_DIR, "traffic",
                                f"{cell['traffic']}.json")
    harness.device_info(cell["chips"])
    compile_cache()
    from repro.stream import StreamConfig, VideoDetector

    arrays = cascade_arrays(cfg)
    det = detector(cfg, arrays)
    levels = reference.pyramid(*reference.bucket(
        *cfg["frame_hw"], cfg["pad_multiple"]), cfg["scale_factor"])
    raw = RawRecorder()
    rows = []            # (camera, frame, program (sym, union), control)
    refs: dict = {}
    for c, cam in enumerate(cameras(cfg, traffic)):
        vd = VideoDetector(det, StreamConfig(
            threshold=0.0, device_state=True,
            keyframe_interval=traffic["keyframe_interval"]))
        for j in range(args.frames):
            f = cam.frame(j)
            rects, _stats = vd.process(f.astype(np.float32))
            key = f.tobytes()
            if key not in refs:
                want = reference.evaluate(f, arrays, cfg)
                ctl = reference.evaluate(f, arrays, cfg, "bfloat16")
                refs[key] = tuple(
                    set(map(tuple, reference.rects_of(x.survivors,
                                                      levels).tolist()))
                    for x in (want, ctl))
            want, ctl = refs[key]
            have = set(map(tuple, raw.of(rects).tolist()))
            rows.append((c, j, off(have, want), off(ctl, want)))
        prog = np.asarray([r[2] for r in rows if r[0] == c])
        ctl = np.asarray([r[3] for r in rows if r[0] == c])
        print(json.dumps({"camera": c, "frames": args.frames,
                          "program_off": prog[:, 0].sum() / max(
                              prog[:, 1].sum(), 1),
                          "control_off": ctl[:, 0].sum() / max(
                              ctl[:, 1].sum(), 1),
                          "survivors": int(prog[:, 1].sum())}), flush=True)
    raw.close()
    n = traffic["check_frames"]
    prog = np.asarray([r[2] for r in rows], np.int64)
    ctl = np.asarray([r[3] for r in rows], np.int64)
    worst, best = 0.0, np.inf
    for seed in range(args.seeds):
        pick = np.random.default_rng(seed).choice(len(rows), n,
                                                  replace=False)
        worst = max(worst, prog[pick, 0].sum() / max(prog[pick, 1].sum(), 1))
        best = min(best, ctl[pick, 0].sum() / max(ctl[pick, 1].sum(), 1))
    print(json.dumps({
        "frames": len(rows), "distinct": len(refs),
        "program_pooled": prog[:, 0].sum() / max(prog[:, 1].sum(), 1),
        "program_largest_sample": worst,
        "control_pooled": ctl[:, 0].sum() / max(ctl[:, 1].sum(), 1),
        "control_smallest_sample": best,
        "limit": cfg["limits"]["frame_survivors_off"]}), flush=True)


if __name__ == "__main__":
    main()
