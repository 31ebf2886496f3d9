"""One-shot photos served through ``DetectorService``: the backlog driver.

A photo-ingest service clearing a backlog.  The service runs its own
background flusher (``start()``); the generator keeps ``depth`` requests
queued whenever a flush takes the queue, in whole batches, drawn from the
mix's pool of photos (``pool_seed``) in an order that ``--seed`` draws anew
for every pass.  Every seed sends the same photos, so a run's work does not
depend on its seed.  Requests of one batch complete together, so the
batch completions are the clock of the throughput: ``images_per_s`` is the
number of images completed after the first batch completion, up to the
completion of the last batch that began inside the window, over the time
between those two completions.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import counts, reference, scenes
from bench.harness import Outcome, span


class Recorder:
    """Keeps what the timed path produced: each batch program's result and
    the images it ran on.  With ``spans``, also marks the service's flushes
    and batch calls in the profiler trace."""

    def __init__(self, svc, det, spans: bool):
        self.chunks: list[dict] = []
        batch_result, detect_batch = det.batch_result, det.detect_batch

        def recorded(images):
            t = time.perf_counter()
            with span("bench.batch_program", spans):
                res = batch_result(images)
            self.chunks.append({"begin": t, "ids": [id(im) for im in images],
                                "res": res})
            return res

        det.batch_result = recorded
        if spans:
            flush = svc.flush

            def traced_detect(images, *a, **k):
                with span("bench.detect_batch", True):
                    return detect_batch(images, *a, **k)

            def traced_flush(*a, **k):
                with span("bench.flush", True):
                    return flush(*a, **k)

            det.detect_batch = traced_detect
            svc.flush = traced_flush


def detector(cfg: dict, arrays: dict):
    """The program under test: a ``Detector`` over the configuration's
    cascade, at its settings, every other option left to the platform."""
    from repro.core import Detector, EngineConfig
    from repro.core.cascade import make_cascade

    cascade = make_cascade(*(arrays[k] for k in (
        "rect_xywh", "rect_w", "wc_threshold", "left_val", "right_val",
        "stage_offsets", "stage_threshold")))
    return Detector(cascade, EngineConfig(
        step=cfg["step"], scale_factor=cfg["scale_factor"],
        min_neighbors=cfg["min_neighbors"], pad_multiple=cfg["pad_multiple"]))


def build(cfg: dict, arrays: dict, traffic: dict):
    from repro.serve import DetectorService, PodSpec, ServiceConfig

    det = detector(cfg, arrays)
    svc = DetectorService(det, ServiceConfig(
        pods=(PodSpec("chip0"),), max_batch=traffic["batch"],
        batch_sizes=(traffic["batch"],)))
    return det, svc


def run(cfg: dict, arrays: dict, traffic: dict, seed: int, seconds: float,
        tracer, t_process: float) -> Outcome:
    h, w = cfg["frame_hw"]
    batch, depth = traffic["batch"], traffic["depth"]
    if depth % batch:
        raise ValueError("the queue depth must be whole batches")
    t_pool = time.perf_counter()
    pool = scenes.photo_pool(traffic["pool_seed"], traffic["pool"], h, w,
                             tuple(traffic["faces"]))
    t_warm = time.perf_counter()
    det, svc = build(cfg, arrays, traffic)
    # the one program the window runs: a full batch at this bucket
    det.detect_batch([p.astype(np.float32) for p in pool[:batch]])
    t_warmed = time.perf_counter()
    builds0 = det.program_builds
    rec = Recorder(svc, det, spans=tracer is not None)

    reqs: list[tuple[object, int]] = []
    svc.start()
    if tracer is not None:
        tracer.start()
    window = span("bench.window", tracer is not None)
    window.__enter__()
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    deadline = t0 + seconds
    i = 0
    while time.perf_counter() < deadline:
        # holding the flush lock, no flush is running: every unfinished
        # request is queued, and the next flush takes whole batches
        with span("bench.refill", tracer is not None), svc._flush_lock:
            pending = [r for r, _ in reqs if not r.done.is_set()]
            n = depth - len(pending) if time.perf_counter() < deadline else 0
            for _ in range(n):
                p = photo_order(seed, i, len(pool))
                req = svc.submit(pool[p])
                reqs.append((req, p))
                pending.append(req)
                i += 1
        if pending:
            with span("bench.wait", tracer is not None):
                pending[0].done.wait(max(deadline - time.perf_counter(), 0))
    # the batch that began last inside the window has to finish
    while True:
        started = [c for c in rec.chunks if c["begin"] < deadline]
        by_id = {id(r.image): r for r, _ in reqs}
        last = [by_id[k] for k in started[-1]["ids"]] if started else []
        if last and all(r.done.is_set() for r in last):
            break
        time.sleep(0.01)
    t_end = time.perf_counter()
    window.__exit__(None, None, None)
    rows = tracer.stop() if tracer is not None else None
    svc.stop()
    builds = det.program_builds - builds0

    out = Outcome(setup_s=setup_s, attempted=len(reqs))
    out.failed = sum(1 for r, _ in reqs if r.error is not None)
    out.notes.append(f"program builds inside the window: {builds}")
    out.notes.append(
        f"set-up {setup_s:.3f} s: start-up and device {t_pool - t_process:.3f}"
        f" s, photo pool {t_warm - t_pool:.3f} s, program build and first "
        f"batch {t_warmed - t_warm:.3f} s")
    # batch completions: the clock of the throughput
    by_id = {id(r.image): (r, p) for r, p in reqs}
    chunks = []
    for c in rec.chunks:
        members = [by_id[k] for k in c["ids"]]
        chunks.append({"begin": c["begin"], "members": members,
                       "end": max(r.t_done for r, _ in members),
                       "res": c["res"]})
    counted = [c for c in chunks if c["begin"] < deadline]
    if len(counted) < 2:
        raise RuntimeError(f"{len(counted)} batch(es) began in a window of "
                           f"{seconds} s; the throughput needs two")
    images = sum(len(c["members"]) for c in counted[1:])
    span_s = counted[-1]["end"] - counted[0]["end"]
    out.e2e["images_per_s"] = images / span_s
    out.notes.append(
        f"window: {len(counted)} batches of {batch} began in {seconds} s; "
        f"{images} images completed in the {span_s:.3f} s between the first "
        f"and the last of their completions; {len(chunks)} batches in all; "
        f"requests attempted {len(reqs)}, failed {out.failed}; the generator "
        f"ran {t_end - deadline:.3f} s past the window waiting for the last "
        f"batch")

    # what the timed path produced, per request, on the host
    served = []      # (request, pool index, survivors (lvl, y, x), counts)
    for c in chunks:
        res = c["res"]
        val = np.asarray(res.valid)
        img = np.asarray(res.img)[val]
        surv = np.stack([np.asarray(a)[val] for a in (res.lvl, res.ys,
                                                     res.xs)], axis=1)
        alive = np.asarray(res.alive_counts)
        for b, (r, p) in enumerate(c["members"]):
            s = surv[img == b]
            served.append((r, p, s[np.lexsort(s.T[::-1])], alive[:, b]))
        c["res"] = None
    n_windows = sum(int(v.sum()) for *_x, v in reference.levels_of(
        pool[0], cfg))
    out.ctx.update(served=[
        (r.t_done, counts.useful_ops(counts.entering(a, n_windows), arrays))
        for r, _p, _s, a in served if r.error is None],
        trace_rows=rows, t0_host=t0, arrays=arrays,
        counted=[(c["begin"], c["end"], len(c["members"])) for c in counted])
    out.memory_peak_bytes = memory_peak()
    rec.chunks.clear()
    del det, svc, rec
    gc.collect()
    out.checks = check(served, pool, cfg, arrays, traffic, seed)
    return out


def photo_order(seed: int, i: int, n: int) -> int:
    """The photo of request ``i``: the pool in a new order from the seed on
    every pass, so each photo meets every position of a batch."""
    return int(np.random.default_rng([seed, i // n]).permutation(n)[i % n])


def memory_peak() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def check(served, pool, cfg, arrays, traffic, seed, refs=None) -> dict:
    """Compare what the timed path produced with the plain reference.

    For a sample of the served photos drawn from the seed: the surviving
    windows and the per-stage alive counts of every request of those
    photos against the reference's.  And for each of those requests, the
    rects it returned against the reference's grouping of the windows the
    program kept.  ``refs`` keeps the reference's frames by photo."""
    lim = cfg["limits"]
    failed = sum(1 for r, *_ in served if r.error is not None)
    ok = [x for x in served if x[0].error is None]
    photos = sorted({p for _r, p, _s, _a in ok})
    rng = np.random.default_rng(seed)
    sample = sorted(rng.choice(photos, min(traffic["check_photos"],
                                           len(photos)), replace=False))
    levels = reference.pyramid(*reference.bucket(
        *cfg["frame_hw"], cfg["pad_multiple"]), cfg["scale_factor"])
    sym = union = count_gap = count_ref = rects_off = n_checked = 0
    refs = {} if refs is None else refs
    for p in sample:
        if p not in refs:
            refs[p] = reference.evaluate(pool[p], arrays, cfg)
        ref = refs[p]
        want = set(map(tuple, ref.survivors.tolist()))
        for r, _p, surv, alive in (x for x in ok if x[1] == p):
            got = set(map(tuple, surv.tolist()))
            sym += len(got ^ want)
            union += len(got | want)
            count_gap += int(np.abs(alive.astype(np.int64)
                                    - ref.counts).sum())
            count_ref += int(ref.counts.sum())
            grouped = reference.group(reference.rects_of(surv, levels),
                                      cfg["min_neighbors"])
            rects_off += not same_rects(r.rects, grouped)
            n_checked += 1
    return {
        "requests_failed": (failed, 0),
        "survivors_off": (sym / max(union, 1), lim["survivors_off"]),
        "alive_counts_off": (count_gap / max(count_ref, 1),
                             lim["alive_counts_off"]),
        "rects_off": (rects_off, 0),
        "unchecked": (int(n_checked == 0), 0),
    }


def same_rects(a, b) -> bool:
    a = np.asarray(a, np.int64).reshape(-1, 4)
    b = np.asarray(b, np.int64).reshape(-1, 4)
    return (a.shape == b.shape
            and np.array_equal(a[np.lexsort(a.T[::-1])],
                               b[np.lexsort(b.T[::-1])]))
