"""Quickstart: the paper's face detector in five lines, plus the two
execution engines and the scheduling/energy layer.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import Detector, EngineConfig
from repro.core.training.data import render_scene
from repro.configs.viola_jones import pretrained
from repro.scheduling import (build_detection_dag, simulate, odroid_xu4,
                              rpi3b, SequentialScheduler, BotlevScheduler)
from repro.compile_cache import use_compile_cache


def main() -> None:
    # 1) load the AdaBoost-trained cascade and render a test scene
    cascade, meta = pretrained()
    print(f"cascade: {cascade.n_stages} stages, {cascade.n_weak} weak "
          f"classifiers (trained DR={meta['overall_dr']:.3f}, "
          f"FPR={meta['overall_fpr']:.2e})")
    img, gt = render_scene(np.random.default_rng(3), 128, 128, n_faces=1)

    # 2) detect — wave engine (TPU-style compaction), then the paper's
    #    dense delayed-rejection baseline
    det = Detector(cascade, EngineConfig(mode="wave", step=2,
                                         scale_factor=1.25,
                                         min_neighbors=2))
    boxes = det.detect(img)
    print(f"ground truth: {gt.tolist()}")
    print(f"detections:   {boxes.tolist()}")

    # 3) the asymmetric-scheduling layer: modeled time/energy on the
    #    paper's two boards
    dag = build_detection_dag(128, 128, cascade.stage_sizes(), step=2,
                              scale_factor=1.25)
    for name, plat in (("Odroid XU4", odroid_xu4()), ("RPi 3B+", rpi3b())):
        seq = simulate(dag, plat, SequentialScheduler())
        bot = simulate(dag, plat, BotlevScheduler())
        print(f"{name}: sequential {seq.makespan:.2f}s/{seq.energy:.1f}J → "
              f"Botlev {bot.makespan:.2f}s/{bot.energy:.1f}J "
              f"({100 * (1 - bot.makespan / seq.makespan):.0f}% faster)")


if __name__ == "__main__":
    use_compile_cache()
    main()
