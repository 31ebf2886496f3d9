"""Host spans of the served path (``repro.obs``): nesting and parents across
threads, the ring's bound, JAX's compile events, the spans' place in a
profiler trace, and the spans a ``DetectorService`` flush records."""

import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import Detector, EngineConfig, paper_shaped_cascade
from repro.core.training.data import render_scene
from repro.serve import DetectorService, PodSpec, ServiceConfig


def new_spans(before: list) -> list:
    seen = {s.id for s in before}
    return [s for s in obs.spans() if s.id not in seen]


def test_nesting_and_parents_across_two_threads():
    before = obs.spans()
    barrier = threading.Barrier(2, timeout=30)

    def work(k):
        with obs.span("test.outer", flush=k):
            barrier.wait()          # both outer spans open at once
            with obs.span("test.inner", n=k):
                barrier.wait()
            obs.interval("test.after", 1, 2, req=k)

    threads = [threading.Thread(target=work, args=(k,)) for k in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    got = new_spans(before)
    assert len(got) == 6
    for k in (1, 2):
        outer, = [s for s in got if s.name == "test.outer"
                  and s.attrs["flush"] == k]
        inner, = [s for s in got if s.name == "test.inner"
                  and s.attrs["n"] == k]
        after, = [s for s in got if s.name == "test.after"
                  and s.attrs["req"] == k]
        assert outer.parent == 0
        assert inner.parent == outer.id and after.parent == outer.id
        assert inner.thread == outer.thread == after.thread
        # the flush's work carries the flush's id
        assert inner.attrs == {"n": k, "flush": k}
        assert after.attrs == {"req": k, "flush": k}
        assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns
    assert len({s.thread for s in got}) == 2


def test_a_span_is_recorded_when_its_block_raises():
    before = obs.spans()
    with pytest.raises(ValueError):
        with obs.span("test.raises", n=3):
            raise ValueError("boom")
    got = new_spans(before)
    assert [(s.name, s.attrs) for s in got] == [("test.raises", {"n": 3})]


def test_the_ring_is_bounded_and_counts_what_it_drops():
    held, dropped = len(obs.spans()), obs.dropped()
    n = obs.RING_SIZE + 10
    for i in range(n):
        obs.interval("test.fill", i, i + 1, n=i)
    ring = obs.spans()
    assert len(ring) == obs.RING_SIZE
    assert obs.dropped() - dropped == held + n - obs.RING_SIZE
    assert ring[-1].attrs["n"] == n - 1
    assert ring[0].attrs["n"] == 10     # the oldest went first


def test_a_fresh_jit_records_its_trace_lower_and_compile():
    def obs_compile_probe(x):
        return x * 3 + 1

    before = obs.spans()
    t0 = time.perf_counter_ns()
    jax.block_until_ready(jax.jit(obs_compile_probe)(jnp.ones(7)))
    t1 = time.perf_counter_ns()
    got = [s for s in new_spans(before)
           if "obs_compile_probe" in s.attrs.get("fun_name", "")]
    names = [s.name for s in got]
    for name in ("jax.trace", "jax.lower", "jax.compile"):
        assert names.count(name) >= 1, names
    for s in got:
        # JAX's wall-clock events land on the ring's clock
        assert t0 - 5_000_000 <= s.t0_ns <= s.t1_ns <= t1 + 5_000_000
    first = {n: min(s.t0_ns for s in got if s.name == n)
             for n in ("jax.trace", "jax.lower", "jax.compile")}
    assert first["jax.trace"] <= first["jax.lower"] <= first["jax.compile"]


def test_a_span_appears_in_the_profiler_trace_where_the_ring_puts_it(
        tmp_path):
    from jax.profiler import ProfileData

    before = obs.spans()
    jax.profiler.start_trace(str(tmp_path))
    try:
        # the anchor, as the benchmark's window span: its start on the
        # profiler's clock and a perf_counter reading taken right after
        with jax.profiler.TraceAnnotation("test.anchor"):
            anchor_ns = time.perf_counter_ns()
            time.sleep(0.02)
            with obs.span("test.profiled", n=1):
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    ring, = [s for s in new_spans(before) if s.name == "test.profiled"]
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("test.anchor", "test.profiled"):
                    events[ev.name] = ev
    assert set(events) == {"test.anchor", "test.profiled"}
    mapped = int(events["test.anchor"].start_ns) + ring.t0_ns - anchor_ns
    assert abs(mapped - int(events["test.profiled"].start_ns)) < 1_000_000
    assert abs(int(events["test.profiled"].duration_ns)
               - (ring.t1_ns - ring.t0_ns)) < 1_000_000


CASC = paper_shaped_cascade(0, stage_sizes=[3, 4, 5])
HW = (64, 80)


@pytest.fixture(scope="module")
def flushed():
    """One flush of four requests in chunks of two, and the spans it
    recorded."""
    det = Detector(CASC, EngineConfig(mode="wave", pad_multiple=32, step=2,
                                      scale_factor=1.3, min_neighbors=2))
    svc = DetectorService(det, ServiceConfig(
        pods=(PodSpec("chip0"),), max_batch=2, batch_sizes=(2,)))
    rng = np.random.default_rng(4)
    images = [render_scene(rng, *HW, n_faces=1)[0] for _ in range(4)]
    svc.detect_many(images[:2])             # builds the program
    before = obs.spans()
    reqs = [svc.submit(im) for im in images]
    assert svc.flush() == 4
    assert all(r.error is None for r in reqs)
    return det, images, reqs, new_spans(before)


def test_a_flush_records_queue_wait_once_per_request(flushed):
    _det, _images, reqs, got = flushed
    flush, = [s for s in got if s.name == "serve.flush"]
    assert flush.attrs == {"flush": 2, "n": 4}
    waits = [s for s in got if s.name == "serve.queue"]
    assert sorted(s.attrs["req"] for s in waits) == [r.req_id for r in reqs]
    for s, r in zip(sorted(waits, key=lambda s: s.attrs["req"]), reqs):
        assert s.parent == flush.id and s.attrs["flush"] == 2
        assert s.t0_ns == int(r.t_submit * 1e9)
        assert flush.t0_ns <= s.t1_ns <= flush.t1_ns
    # the second chunk's requests waited behind the first chunk
    assert min(s.t1_ns for s in waits[2:]) > max(s.t1_ns for s in waits[:2])


def test_the_engine_spans_nest_inside_the_flush(flushed):
    det, images, _reqs, got = flushed
    flush, = [s for s in got if s.name == "serve.flush"]
    by_name = {}
    for s in got:
        by_name.setdefault(s.name, []).append(s)
    assert len(by_name["engine.pack"]) == 2         # one per chunk
    assert len(by_name["engine.fetch"]) == 2
    assert len(by_name["engine.decode"]) == 2
    assert len(by_name["nms.group"]) == 4           # one per photo
    for name in ("engine.pack", "engine.fetch", "engine.decode",
                 "nms.group"):
        for s in by_name[name]:
            assert s.parent == flush.id, name
            assert s.attrs["flush"] == 2
            assert s.thread == flush.thread
            assert flush.t0_ns <= s.t0_ns <= s.t1_ns <= flush.t1_ns
    pack, fetch, decode = (sorted(by_name[n], key=lambda s: s.t0_ns)
                           for n in ("engine.pack", "engine.fetch",
                                     "engine.decode"))
    for p, f, d in zip(pack, fetch, decode):
        assert p.t1_ns <= f.t0_ns <= f.t1_ns <= d.t0_ns
    raw = (det.detect_batch(images[:2], group=False)
           + det.detect_batch(images[2:], group=False))
    assert [s.attrs["n"] for s in sorted(by_name["nms.group"],
                                         key=lambda s: s.t0_ns)] == [
        len(r) for r in raw]


def test_pack_and_fetch_count_the_bytes_they_move(flushed):
    det, _images, _reqs, got = flushed
    hp, wp = det._bucket_hw(*HW)
    b = 2
    for s in got:
        if s.name == "engine.pack":
            assert s.attrs == {"n": b, "bytes": b * hp * wp * 4 + b * 8,
                               "flush": 2}
    rng = np.random.default_rng(5)
    res = det.batch_result([render_scene(rng, *HW, n_faces=1)[0]
                            for _ in range(b)])
    lanes = sum(a.nbytes for a in (res.valid, res.img, res.lvl, res.ys,
                                   res.xs))
    assert lanes == det.batch_plan(hp, wp, b).capacities[0] * (4 * 4 + 1)
    # and the head's work counter: two int32 per image
    assert res.head_work.nbytes == b * 8
    want = lanes + res.head_work.nbytes
    fetches = [s for s in got if s.name == "engine.fetch"]
    assert [s.attrs["bytes"] for s in fetches] == [want, want]
    for s in fetches:
        assert 0 < s.attrs["head_work"] <= s.attrs["head_dense"]


def test_a_device_stream_records_step_poll_fetch_and_decode():
    """Each frame of a device-resident stream dispatches under
    ``stream.step`` (its session and frame), copies its verdict, whether
    the step rebuilt the survivor-slot list and the head's counters under
    ``stream.poll``, and, when the step evaluated
    windows, copies the survivor slots (``stream.fetch``, with their
    bytes) and decodes and groups them (``stream.decode`` around
    ``nms.group``)."""
    from repro.stream import StreamConfig, VideoDetector

    det = Detector(paper_shaped_cascade(0, stage_sizes=[3, 4, 5]),
                   EngineConfig(step=2, scale_factor=1.3, min_neighbors=2))
    vd = VideoDetector(det, StreamConfig(tile=16, keyframe_interval=0,
                                         full_refresh_frac=0.9,
                                         device_state=True))
    rng = np.random.default_rng(5)
    scene = rng.integers(0, 256, (64, 96)).astype(np.float32)
    moved = scene.copy()
    moved[2:6, 2:6] = 0.0
    before = obs.spans()
    modes = [vd.process(f)[1].mode for f in (scene, scene, moved)]
    assert modes == ["full", "cached", "incremental"]
    got = new_spans(before)
    steps = [s for s in got if s.name == "stream.step"]
    assert [s.attrs["frame"] for s in steps] == [0, 1, 2]
    assert {s.attrs["sess"] for s in steps} == {vd.sid}
    polls = [s for s in got if s.name == "stream.poll"]
    assert [s.attrs["mode"] for s in polls] == [2, 0, 1]
    n_weak = np.diff(np.asarray(det.cascade.stage_offsets))
    for p in polls:
        assert len(p.attrs["head_windows"]) == len(n_weak)
        assert 0 <= p.attrs["head_work"] <= p.attrs["head_dense"]
    assert polls[1].attrs["head_dense"] == 0          # nothing ran
    built = [p.attrs["slots_built"] for p in polls]
    assert built[1] == 0 and set(built) <= {0, 1}     # cached: list kept
    assert vd.slot_builds == sum(built)
    assert polls[0].attrs["head_dense"] >= polls[2].attrs["head_dense"] > 0
    fetches = [s for s in got if s.name == "stream.fetch"]
    assert len(fetches) == 2                  # the full and the changed frame
    assert all(s.attrs["bytes"] == vd._splan.decode_cap * 4 for s in fetches)
    decodes = {s.id for s in got if s.name == "stream.decode"}
    groups = [s for s in got if s.name == "nms.group"]
    assert len(decodes) == 2 and all(g.parent in decodes for g in groups)
