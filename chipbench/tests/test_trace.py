"""The trace reducer: busy/idle union, self times, gap attribution, on hand-made events and on a
small recorded trace (``data/trace_small.json``: a slice of a v5e trace of the Anakin cell)."""

import json
from pathlib import Path

import pytest

from chipbench import trace

ITER, MARK = trace.ITER, trace.MARK


def test_union_merges_overlaps_and_drops_empty_intervals():
    assert trace.union([(5, 7), (0, 3), (2, 4), (9, 9), (7, 8)]) == [(0, 4), (5, 8)]


def test_short_name_keeps_the_op_and_drops_its_hlo_text():
    assert trace.short_name("%fusion.956 = (f32[]{:T(128)}, bf16[4,4,3,32]) fusion(bf16[16384] %x), kind=kOutput") == "fusion.956"
    assert trace.short_name("copy.4") == "copy.4"


def test_self_times_take_children_out_of_their_container():
    events = [("while.1", 0, 100), ("fusion.a", 10, 30), ("fusion.b", 50, 20), ("copy.c", 120, 10), ("fusion.a", 140, 5)]
    assert trace.self_times(events) == {"while.1": 50, "fusion.a": 35, "fusion.b": 20, "copy.c": 10}


def test_reduce_busy_idle_and_gap_labels():
    marks = [(ITER, 0, 1000), (MARK + "env.step", 100, 300), (MARK + "prog", 400, 500), (ITER, 1000, 1000)]
    ops = [("a", 400, 100), ("b", 450, 50), ("b", 500, 100), ("c", 700, 200), ("d", 1500, 500)]  # busy 400..600, 700..900, 1500..2000
    out = trace.reduce({"device": {"/device:TPU:0": ops}, "host": {"marks": marks}})
    assert out["window_s"] == pytest.approx(2000e-9)
    assert out["busy_s"] == pytest.approx(900e-9)
    gaps = dict(out["idle_gaps"])
    # 0..400: env.step covers 300 of 400 -> env.step; 600..700 inside prog; 900..1500: no mark covers half -> loop
    assert gaps == {"env.step": pytest.approx(400e-9), "prog": pytest.approx(100e-9), "loop": pytest.approx(600e-9)}
    assert dict(out["device_ops"]) == {"a": pytest.approx(50e-9), "b": pytest.approx(150e-9), "c": pytest.approx(200e-9), "d": pytest.approx(500e-9)}


def test_reduce_means_over_device_planes_and_clips_to_the_marked_span():
    marks = [(ITER, 100, 800)]
    planes = {"/device:TPU:0": [("a", 0, 500)], "/device:TPU:1": [("a", 700, 1000)]}  # clipped: 400 and 200 of 800
    out = trace.reduce({"device": planes, "host": {"marks": marks}})
    assert out["busy_s"] == pytest.approx(300e-9) and out["window_s"] == pytest.approx(800e-9)


def test_reduce_returns_nothing_without_marks_or_device_events():
    assert trace.reduce({"device": {}, "host": {"marks": [(ITER, 0, 10)]}}) is None
    assert trace.reduce({"device": {"/device:TPU:0": [("a", 0, 5)]}, "host": {"marks": []}}) is None


def test_recorded_trace_against_a_brute_force_count():
    recorded = json.loads((Path(__file__).parent / "data" / "trace_small.json").read_text())
    ops = [tuple(e) for e in recorded["device"]["/device:TPU:0"]]
    marks = [tuple(m) for m in recorded["host"]["marks"]]
    out = trace.reduce({"device": {"/device:TPU:0": ops}, "host": {"marks": marks}})
    t0, t1 = min(s for n, s, d in marks if n == ITER), max(s + d for n, s, d in marks if n == ITER)
    # brute force at 100 ns: a tick is busy when any op covers it
    step = 100
    busy = [False] * ((t1 - t0) // step + 1)
    for _, s, d in ops:
        for k in range(max(s, t0) // step, min(s + d, t1) // step):
            busy[k - t0 // step] = True
    assert out["busy_s"] * 1e9 == pytest.approx(sum(busy) * step, rel=0.02)
    assert out["window_s"] * 1e9 == t1 - t0
    # the recorded slice holds the boundary between two dispatches: the chip waits there for the host loop
    gaps = dict(out["idle_gaps"])
    assert gaps["loop"] > 10 * gaps.get("ppo.anakin_phase", 0.0)
    assert sum(gaps.values()) == pytest.approx(out["window_s"] - out["busy_s"])
    assert sum(v for _, v in out["device_ops"]) <= out["busy_s"] * 1.0001  # self times never count a nested op twice
    assert all(len(name) <= 120 and " = " not in name for name, _ in out["device_ops"])
