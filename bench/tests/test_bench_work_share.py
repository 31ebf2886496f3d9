"""The reader of ``fused_head.work_share.photos`` on hand-built rings: the
``head_work`` and ``head_dense`` attributes of the program's
``engine.fetch`` spans, summed over the batches ``images_per_s`` counts."""

from __future__ import annotations

import sys

import pytest

from bench.harness import read_metric
from repro import obs

NAME = "fused_head.work_share.photos"
S = 1_000_000_000


def ctx() -> dict:
    return {"counted": [(8.125, 8.875, 8), (8.875, 9.5, 8)]}


def ring(with_counts: bool = True) -> list:
    out = []

    def fetch(t0_s, **attrs):
        t0 = int(t0_s * S)
        out.append(obs.Span("engine.fetch", t0, t0 + S // 64, 1,
                            len(out) + 1, 0, attrs))

    counts = ({"head_work": 1_000, "head_dense": 8_000},
              {"head_work": 3_000, "head_dense": 8_000},
              {"head_work": 8_000, "head_dense": 8_000})
    # two fetches in the counted batches, one before and one after them
    for t0, c in zip((8.1328125, 8.8828125, 7.0), counts):
        fetch(t0, bytes=117_317_560, **(c if with_counts else {}))
    fetch(9.5, bytes=1, **(counts[2] if with_counts else {}))
    return out


def test_work_share_over_the_counted_batches(monkeypatch):
    monkeypatch.setattr(obs, "spans", ring)
    assert read_metric(NAME, ctx()) == 100 * 4_000 / 16_000


def test_a_program_without_the_counter_reads_nothing(monkeypatch):
    # the fetch spans of a program whose head has no exit carry no counts
    monkeypatch.setattr(obs, "spans", lambda: ring(with_counts=False))
    assert read_metric(NAME, ctx()) is None


@pytest.mark.parametrize("spans", [lambda: [], ring], ids=["empty", "ring"])
def test_nothing_to_read(monkeypatch, spans):
    monkeypatch.setattr(obs, "spans", spans)
    c = ctx()
    if spans is ring:
        c["counted"] = []
    assert read_metric(NAME, c) is None


def test_without_the_program_module(monkeypatch):
    monkeypatch.setattr(obs, "spans", ring)
    monkeypatch.delattr(sys.modules["repro"], "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read_metric(NAME, ctx()) is None
