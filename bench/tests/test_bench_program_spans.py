"""The readers of the program's own spans (``repro.obs``) on a hand-built
run: two device ops in a 1 s traced window, two counted batches of 8
photos, and a ring of spans around them, some inside the counted batches
and some outside.  Host second 8.0 is trace time 0; every time is a
multiple of 1/128 s, so that the mapping between the clocks is exact."""

from __future__ import annotations

import sys

import pytest

from bench.harness import read_metric
from repro import obs

S = 1_000_000_000
MS = 1_000_000
READERS = ["serve.queue_wait_ms.photos", "device.idle_in_flush.photos",
           "engine.pack.ms_per_image.photos",
           "engine.h2d_mb_per_image.photos",
           "engine.fetch.ms_per_image.photos",
           "engine.d2h_mb_per_image.photos",
           "host.decode_nms.ms_per_image.photos", "engine.build_s.photos"]


def op(start_ms: float, end_ms: float) -> dict:
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": "fusion",
            "start_ns": int(start_ms * MS),
            "dur_ns": int((end_ms - start_ms) * MS)}


def ctx() -> dict:
    # busy [0, 250) and [500, 875) ms of the window [0, 1000) ms
    return {"trace_rows": [op(0, 250), op(500, 875)],
            "t0_ns": 0, "t1_ns": S, "t0_host": 8.0, "window_s": 1.0,
            "counted": [(8.125, 8.875, 8), (8.875, 9.5, 8)]}


def ring() -> list:
    out = []

    def add(name, t0_s, dur_ns, parent=0, **attrs):
        t0 = int(t0_s * S)
        out.append(obs.Span(name, t0, t0 + dur_ns, 1, len(out) + 1, parent,
                            attrs))
        return len(out)

    # set-up: trace and lower overlap (a nested jit), then a cache load;
    # a compile that ends inside the window is not set-up
    add("jax.trace", 1.0, S)
    add("jax.lower", 1.5, S)
    add("jax.compile", 3.0, S // 4)
    add("jax.compile", 7.5, S // 2)
    # flushes: [125, 625) ms and [937.5, 1500) ms on the trace's clock
    # overlap idle [250, 500) and [937.5, 1000); one before the window
    add("serve.flush", 7.0, S // 2, flush=1)
    add("serve.flush", 8.125, S // 2, flush=2)
    add("serve.flush", 8.9375, S * 9 // 16, flush=3)
    # queue waits: two end in the counted batches (the first at their
    # start); one ends at their end, one before
    add("serve.queue", 7.5, 625 * MS, req=1)
    add("serve.queue", 7.5, 1375 * MS, req=2)
    add("serve.queue", 8.0, 1500 * MS, req=3)
    add("serve.queue", 7.5, 400 * MS, req=4)
    # engine spans of the two counted batches, and of batches outside
    for t0 in (8.125, 8.875):
        add("engine.pack", t0, (3 if t0 < 8.5 else 5) * MS, n=8,
            bytes=9_830_464)
        add("engine.fetch", t0 + 0.0078125, 16 * MS, bytes=117_317_544)
    for t0 in (7.0, 9.5):
        add("engine.pack", t0, 11 * MS, n=8, bytes=1)
        add("engine.fetch", t0, 13 * MS, bytes=1)
        add("engine.decode", t0, 17 * MS)
        add("nms.group", t0, 19 * MS, n=5)
    add("engine.decode", 8.15625, 4 * MS)
    nms = add("nms.group", 8.171875, 6 * MS, n=12_000)
    add("jax.compile", 8.1796875, MS, parent=nms)   # not its own time
    return out


@pytest.mark.parametrize("name,want", [
    ("serve.queue_wait_ms.photos", 1000.0),
    ("device.idle_in_flush.photos", 31.25),
    ("engine.pack.ms_per_image.photos", 0.5),
    ("engine.h2d_mb_per_image.photos", 2 * 9_830_464 / 1e6 / 16),
    ("engine.fetch.ms_per_image.photos", 2.0),
    ("engine.d2h_mb_per_image.photos", 2 * 117_317_544 / 1e6 / 16),
    ("host.decode_nms.ms_per_image.photos", 9 / 16),
    ("engine.build_s.photos", 1.75),
])
def test_each_reader_on_a_hand_built_ring(monkeypatch, name, want):
    monkeypatch.setattr(obs, "spans", ring)
    assert read_metric(name, ctx()) == want


def test_the_idle_in_flush_is_part_of_the_idle_share(monkeypatch):
    monkeypatch.setattr(obs, "spans", ring)
    c = ctx()
    c["busy_s"] = 0.625
    share = read_metric("device.idle_share.photos", c)
    assert share == 37.5
    assert read_metric("device.idle_in_flush.photos", c) < share


@pytest.mark.parametrize("name", READERS)
def test_each_reader_reads_nothing_from_an_empty_ring(monkeypatch, name):
    monkeypatch.setattr(obs, "spans", lambda: [])
    assert read_metric(name, ctx()) is None


@pytest.mark.parametrize("name", READERS)
def test_each_reader_reads_nothing_without_the_program_module(
        monkeypatch, name):
    # a program without ``repro.obs``: the import fails, and the ring that
    # the module would hold is not read
    monkeypatch.setattr(obs, "spans", ring)
    monkeypatch.delattr(sys.modules["repro"], "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read_metric(name, ctx()) is None
