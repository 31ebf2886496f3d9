#!/usr/bin/env python
"""CI plan-layer smoke: backends selected *through the plan* stay bit-exact,
and the serving stats reflect the plan's choices.

Complements ``crossover_smoke.py`` (which forces backends via
``tail_backend``): here the backend decisions flow the production way —
``EngineConfig.tail_rungs`` ladder -> ``compile_plan`` -> per-segment
``SegmentPlan.backend`` -> executor.  Covers, on the pretrained cascade:

1. hand-built ladders that force each backend at every rung: the packed
   batched engine must be bit-identical across all three, and the
   compiled plans must report the ladder's backend per tail segment;
2. a mixed ladder: the plan picks *different* backends at different
   capacities, exactly as ``repro.plan.select_backend`` dictates;
3. ``DetectorService.warmup(tune_tail=True)``: ``stats().tail`` must
   carry the measured rungs and the plan-chosen per-segment backends of
   the warmed bucket, consistent with the compiled plan.

Exit code 0 = all checks pass.  Run by ``scripts/ci.sh``.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

import repro.plan as planlib  # noqa: E402
from repro.core import Detector, EngineConfig  # noqa: E402
from repro.core.training.data import render_scene  # noqa: E402
from repro.configs.viola_jones import pretrained  # noqa: E402
from repro.serve import DetectorService, ServiceConfig  # noqa: E402

KW = dict(mode="wave", step=2, scale_factor=1.3, min_neighbors=2,
          dense_segments=(1,), tail_backend="auto")


def check_forced_ladders(casc) -> None:
    """Each backend forced through the ladder: identical outputs, and the
    compiled plan reports that backend on every tail segment."""
    rng = np.random.default_rng(0)
    imgs = [render_scene(rng, 96, 96, n_faces=1)[0] for _ in range(3)]
    want = None
    for bk in ("gather", "bulk", "pallas"):
        cfg = EngineConfig(tail_rungs=((10 ** 9, bk),), **KW)
        det = Detector(casc, cfg)
        bplan = det.batch_plan(96, 96, len(imgs))
        assert bplan.tail_segments, "fixture must exercise a packed tail"
        assert all(s.backend == bk for s in bplan.tail_segments), bplan
        got = det.detect_batch(imgs, strategy="packed")
        if want is None:
            want = got
        else:
            assert all(np.array_equal(a, b) for a, b in zip(want, got)), bk
    print("  forced ladders: gather == bulk == pallas through the plan "
          "(batch)")


def check_mixed_ladder(casc) -> None:
    ladder = ((256, "gather"), (2048, "bulk"), (1 << 30, "pallas"))
    cfg = EngineConfig(tail_rungs=ladder, **KW)
    det = Detector(casc, cfg)
    for cap, want in ((100, "gather"), (256, "gather"), (300, "bulk"),
                      (5000, "pallas")):
        got = planlib.select_backend(cfg, cap)
        assert got == want, (cap, got)
    bplan = det.batch_plan(96, 96, 2)
    for seg in bplan.tail_segments:
        assert seg.backend == planlib.select_backend(cfg, seg.capacity)
    print(f"  mixed ladder: plan picks per-capacity backends "
          f"{[(s.capacity, s.backend) for s in bplan.tail_segments]}")


def check_service_stats(casc) -> None:
    rng = np.random.default_rng(1)
    probe = render_scene(rng, 96, 96, n_faces=1)[0]
    det = Detector(casc, EngineConfig(**KW))
    svc = DetectorService(det, ServiceConfig(batch_sizes=(1, 2, 4),
                                             max_batch=4))
    svc.warmup(probe, tune_tail=True)
    st = svc.stats().tail
    cfg = svc.detector.config
    assert cfg.tail_backend == "auto" and cfg.tail_rungs
    assert st.rungs == tuple(tuple(r) for r in cfg.tail_rungs)
    assert st.chosen, "warmup must record plan-chosen backends"
    bplan = svc.detector.batch_plan(96, 96, 4)
    assert st.chosen == tuple((s.capacity, s.backend)
                              for s in bplan.tail_segments)
    for cap, bk in st.chosen:
        assert bk == planlib.select_backend(cfg, cap)
    print(f"  service stats: rungs={st.rungs} chosen={st.chosen}")


def main() -> None:
    casc, _ = pretrained()
    print("plan smoke: backend selection through the plan layer")
    check_forced_ladders(casc)
    check_mixed_ladder(casc)
    check_service_stats(casc)
    print("plan smoke OK")


if __name__ == "__main__":
    main()
