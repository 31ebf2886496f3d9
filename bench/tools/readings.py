"""Readings that set the limits of a photo cell's check, on the chip.

    python3 -m bench.tools.readings --config ff25-trained-vga

Every photo of the cell's pool, served in full batches through
``DetectorService`` by the program the window runs, against the plain
reference: the lower readings.  The same photos with the control, the
reference computed in bfloat16, put in the program's place: the upper
readings.  One JSON line per photo, then the readings of the check as a run
makes it (``bench.photos.check``) pooled over the sample of photos that each
of many seeds draws, which is all that a seed changes: the largest the
program gives and the smallest the control gives.
"""

from __future__ import annotations

import argparse
import json
from types import SimpleNamespace

import numpy as np

from bench import harness, photos, reference, scenes
from bench.cascade import cascade_arrays, load_config
from bench.run import compile_cache


def served_by_program(svc, rec, pool, batch):
    served = []
    for b0 in range(0, len(pool), batch):
        idx = list(range(b0, min(b0 + batch, len(pool))))
        reqs = [svc.submit(pool[p]) for p in idx]
        svc.flush()
        res = rec.chunks.pop()["res"]
        val = np.asarray(res.valid)
        img = np.asarray(res.img)[val]
        surv = np.stack([np.asarray(a)[val] for a in (res.lvl, res.ys,
                                                     res.xs)], axis=1)
        alive = np.asarray(res.alive_counts)
        for b, (r, p) in enumerate(zip(reqs, idx)):
            s = surv[img == b]
            served.append((r, p, s[np.lexsort(s.T[::-1])], alive[:, b]))
    return served


def served_by_control(pool, cfg, arrays):
    levels = reference.pyramid(*reference.bucket(
        *cfg["frame_hw"], cfg["pad_multiple"]), cfg["scale_factor"])
    out = []
    for p, img in enumerate(pool):
        f = reference.evaluate(img, arrays, cfg, "bfloat16")
        rects = reference.group(reference.rects_of(f.survivors, levels),
                                cfg["min_neighbors"])
        out.append((SimpleNamespace(error=None, rects=rects), p,
                    f.survivors, f.counts))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="photos-backlog")
    ap.add_argument("--seeds", type=int, default=10000)
    args = ap.parse_args()
    harness.device_info(1)
    compile_cache()
    cfg = load_config(args.config)
    arrays = cascade_arrays(cfg)
    traffic = harness.load_json(harness.BENCH_DIR, "traffic",
                                f"{args.traffic}.json")
    pool = scenes.photo_pool(traffic["pool_seed"], traffic["pool"],
                             *cfg["frame_hw"], tuple(traffic["faces"]))
    det, svc = photos.build(cfg, arrays, traffic)
    rec = photos.Recorder(svc, det, spans=False)
    sides = {"program": served_by_program(svc, rec, pool, traffic["batch"]),
             "control": served_by_control(pool, cfg, arrays)}
    refs: dict = {}
    one = dict(traffic, check_photos=1)
    parts = {side: [] for side in sides}    # per photo: the check's sums
    for p in range(len(pool)):
        line = {"photo": p}
        for side, served in sides.items():
            checks = photos.check([served[p]], pool, cfg, arrays, one, p,
                                  refs)
            line[side] = {k: v for k, (v, _lim) in checks.items()}
            line[side]["survivors"] = int(len(served[p][2]))
            got = set(map(tuple, served[p][2].tolist()))
            want = set(map(tuple, refs[p].survivors.tolist()))
            parts[side].append((
                len(got ^ want), len(got | want),
                int(np.abs(served[p][3].astype(np.int64)
                           - refs[p].counts).sum()),
                int(refs[p].counts.sum())))
        line["reference_survivors"] = int(len(refs[p].survivors))
        print(json.dumps(line), flush=True)
    # a run checks the photos a seed samples: the same sums, pooled
    for side, part in parts.items():
        pick = max if side == "program" else min
        worst: dict = {}
        for seed in range(args.seeds):
            rng = np.random.default_rng(3_000_000_000 + seed)
            take = rng.choice(len(pool), traffic["check_photos"],
                              replace=False)
            a = np.asarray(part)[take].sum(axis=0)
            for k, v in (("survivors_off", a[0] / max(a[1], 1)),
                         ("alive_counts_off", a[2] / max(a[3], 1))):
                worst[k] = pick(worst.get(k, v), float(v))
        print(json.dumps({"side": side, "seeds": args.seeds,
                          pick.__name__: worst}), flush=True)


if __name__ == "__main__":
    main()
