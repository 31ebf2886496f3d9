"""A backlog run with the timed path broken underneath comes out not
correct; the control (the reference one precision lower, in the program's
place) fails the comparison; a sound run passes it.  The device check is
skipped: the driver runs directly, on the CPU, at a tiny size."""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import pytest

from bench import photos, reference, scenes
from bench.tests import tiny


def test_sound_run_is_correct():
    out = tiny.run_photos()
    assert out.correct, out.checks
    assert out.attempted >= 4 and out.failed == 0
    assert out.e2e["images_per_s"] > 0


def test_half_of_the_batch_left_out(monkeypatch):
    from repro.core.engine import Detector

    batch_result = Detector.batch_result

    def half(self, images):
        res = batch_result(self, images)
        keep = len(images) // 2
        return res._replace(valid=res.valid & (res.img < keep),
                            alive_counts=res.alive_counts.at[:, keep:].set(0))

    monkeypatch.setattr(Detector, "batch_result", half)
    out = tiny.run_photos()
    assert not out.correct
    assert out.checks["alive_counts_off"][0] > 0.2


def test_a_batch_that_returns_its_first_result_unchanged(monkeypatch):
    from repro.core.engine import Detector

    batch_result = Detector.batch_result
    first = []

    def stale(self, images):
        res = batch_result(self, images)
        first.append(res)
        return first[0]

    monkeypatch.setattr(Detector, "batch_result", stale)
    assert not tiny.run_photos(seed=2**34 + 9).correct


def test_an_answer_altered_where_it_is_produced(monkeypatch):
    from repro.core import nms

    group = nms.group_rectangles

    def altered(rects, *a, **k):
        out = group(rects, *a, **k)
        if len(out):
            out = out.copy()
            out[0, 0] += 1
        return out

    monkeypatch.setattr(nms, "group_rectangles", altered)
    out = tiny.run_photos()
    assert not out.correct
    assert out.checks["rects_off"][0] > 0


def served_by_reference(pool, cfg, arrays, precision):
    """(request, photo, survivors, counts) as if the reference computed
    in ``precision`` had served every photo of the pool."""
    levels = reference.pyramid(*reference.bucket(
        *cfg["frame_hw"], cfg["pad_multiple"]), cfg["scale_factor"])
    out = []
    for p, img in enumerate(pool):
        f = reference.evaluate(img, arrays, cfg, precision)
        rects = reference.group(reference.rects_of(f.survivors, levels),
                                cfg["min_neighbors"])
        out.append((SimpleNamespace(error=None, rects=rects), p,
                    f.survivors, f.counts))
    return out


def test_control_fails_and_reference_passes():
    cfg, traffic = tiny.config(), tiny.traffic()
    arrays = tiny.arrays(cfg)
    seed = 2**35 + 3
    pool = scenes.photo_pool(seed, traffic["pool"], *cfg["frame_hw"],
                             tuple(traffic["faces"]))
    for precision, ok in (("float32", True), ("bfloat16", False)):
        served = served_by_reference(pool, cfg, arrays, precision)
        checks = photos.check(served, pool, cfg, arrays, traffic, seed)
        assert all(v <= lim for v, lim in checks.values()) == ok, checks
    assert checks["survivors_off"][0] > 3 * cfg["limits"]["survivors_off"]
    assert np.isfinite(checks["alive_counts_off"][0])


@pytest.mark.parametrize("trace", [0, 1])
def test_command_prints_the_result_last(monkeypatch, capsys, trace):
    """The whole command on the CPU, its device check skipped, at the tiny
    size: the result is the last line on stdout, the compared numbers the
    last lines on stderr."""
    import json

    from bench import harness, run

    real_load = harness.load_json
    monkeypatch.setattr(run, "compile_cache", lambda: None)
    monkeypatch.setattr(harness, "device_info", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})
    monkeypatch.setattr(run, "load_config", lambda name: tiny.config())
    monkeypatch.setattr(harness, "load_json", lambda *p: (
        tiny.traffic() if p[-1] == "photos-backlog.json" else real_load(*p)))
    run.main(["--workload", "ff25-trained-vga.photos-backlog", "--seed",
              str(2**33 + 31), "--seconds", "1.5", "--trace", str(trace)])
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check unchecked:")
    if trace:
        assert "busy_s" in result["device"] and "breakdown" in result
    else:
        assert set(result["metrics"]) == {"images_per_s", "setup_s"}
        assert result["metrics"]["images_per_s"]["unit"] == "images/s"
