"""A configuration and traffic small enough for the CPU: the paper-shaped
cascade's feature generator with three stages, on 64 x 96 photos."""

from __future__ import annotations

import time

from bench.cascade import cascade_arrays, load_config
from bench.harness import load_json, BENCH_DIR


def config(name: str = "ff25-deep-vga") -> dict:
    cfg = load_config(name)
    cfg.update(frame_hw=[64, 96], stage_sizes=[3, 4, 5],
               stage_threshold=[-0.2, 0.1, 0.0])
    return cfg


def arrays(cfg: dict) -> dict:
    return cascade_arrays(cfg)


def traffic() -> dict:
    t = load_json(BENCH_DIR, "traffic", "photos-backlog.json")
    t.update(pool=4, batch=2, depth=4, check_photos=2)
    return t


def run_photos(seed: int = 2**33 + 5, seconds: float = 1.5):
    """One backlog run, without the device check."""
    from bench import photos

    cfg = config()
    return photos.run(cfg, arrays(cfg), traffic(), seed, seconds, None,
                      time.perf_counter())
