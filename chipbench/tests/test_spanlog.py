"""The five per-layer readers of PR 28 on a synthetic span log and a ``Window`` driven by a fake clock."""

import json
from collections import namedtuple
from pathlib import Path

import pytest

from chipbench import spanlog
from chipbench.harness import load_module
from chipbench.window import Window

Rec = namedtuple("Rec", "name start end id parent iteration thread counts")


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def window(iterations, dt=0.25):
    """A closed window of ``iterations`` iterations of ``dt`` seconds, from t=100."""
    clock = Clock()
    w = Window(iterations * dt, clock)
    w.open()
    for _ in range(iterations):
        clock.now += dt
        due = w.boundary()
    assert due
    w.close()
    return w


class Log:
    """Builds records the way the program's tracker does: ids in order, parents by nesting."""

    def __init__(self):
        self.records, self._id = [], 0

    def add(self, name, start, dur_ms, parent=None, iteration=None, counts=None, thread="MainThread"):
        self._id += 1
        self.records.append(Rec(name, start, start + dur_ms / 1e3, self._id, parent, iteration, thread, counts))
        return self._id


def dv3_log(w, iterations):
    """Fenced window: env.step 10 ms, a 30 MB pull, a poll every 2nd iteration; then an unfenced stretch
    in which the env step waits 150 ms longer."""
    log = Log()
    log.add("env.step", 99.0, 500.0, iteration=0)  # set-up: before the window
    t = w.boundaries[0]
    for k in range(iterations + 3):
        fenced = k < iterations
        it = log.add("iter", t + 0.001, 248.0, iteration=k + 1)
        roll = log.add("rollout", t + 0.002, 180.0 if not fenced else 30.0, parent=it, iteration=k + 1)
        log.add("env.step", t + 0.010, 10.0 + (0.0 if fenced else 150.0) + (k % 2), parent=roll, iteration=k + 1)
        disp = log.add("update.dispatch", t + 0.190, 50.0, parent=it, iteration=k + 1)
        log.add("player.sync", t + 0.191, 40.0, parent=disp, iteration=k + 1, counts={"bytes": 30_000_000})
        log.add("player.sync", t + 0.235, 0.01, parent=disp, iteration=k + 1)
        if k % 2 == 0:
            log.add("health.poll", t + 0.241, 3.0, parent=it, iteration=k + 1)
        t += 0.25
    log.add("ckpt.snapshot", w.boundaries[0] + 0.3, 400.0, parent=None, iteration=1, thread="ckpt-writer")
    return log.records


def read(name, log, w, monkeypatch):
    monkeypatch.setattr(spanlog, "records", lambda: log)
    return load_module("metrics", name).read({"window": w})


def test_env_step_is_the_median_inside_the_window_and_the_wait_is_what_the_stretch_adds(monkeypatch):
    w = window(8)
    log = dv3_log(w, 8)
    # inside: 10, 11, 10, 11, ... -> 10.5; stretch: iterations 9, 10, 11 -> 160, 161, 160 -> 160
    assert read("env.step_ms", log, w, monkeypatch) == pytest.approx(10.5)
    assert read("env.step_wait_ms", log, w, monkeypatch) == pytest.approx(160.0 - 10.5)


def test_wait_is_never_under_zero_and_reads_zero_without_a_stretch(monkeypatch):
    w = window(4)
    log = [r for r in dv3_log(w, 4) if r.end <= w.boundaries[-1]]  # an untraced run: nothing after the window
    assert read("env.step_wait_ms", log, w, monkeypatch) == 0.0
    faster = [r._replace(end=r.start + 0.001) if r.name == "env.step" and r.start >= w.boundaries[-1] else r for r in dv3_log(w, 4)]
    assert read("env.step_wait_ms", faster, w, monkeypatch) == 0.0


def test_sync_megabytes_are_the_counted_bytes_over_the_windows_iterations(monkeypatch):
    w = window(8)
    assert read("player.sync_mb_per_iter", dv3_log(w, 8), w, monkeypatch) == pytest.approx(30.0)


def test_stalls_are_summed_over_the_window_and_spread_over_its_iterations(monkeypatch):
    w = window(8)
    log = dv3_log(w, 8)
    # a 3 ms poll in iterations 1, 3, 5, 7 of 8; the writer thread's snapshot is no stall of the loop
    assert read("loop.stall_ms_per_iter", log, w, monkeypatch) == pytest.approx(4 * 3.0 / 8)
    extra = Log()
    extra.add("log.flush", w.boundaries[2] + 0.2, 8.0, iteration=3)
    extra.add("ckpt.save", w.boundaries[3] + 0.2, 20.0, iteration=4)
    extra.add("ckpt.save", w.boundaries[-1] + 0.2, 99.0, iteration=10)  # in the stretch: not counted
    assert read("loop.stall_ms_per_iter", log + extra.records, w, monkeypatch) == pytest.approx((12.0 + 28.0) / 8)


def test_untracked_is_the_iteration_less_its_direct_children(monkeypatch):
    w = window(8)
    log = dv3_log(w, 8)
    # 248 - rollout 30 - dispatch 50 - poll 3 (every 2nd) -> 165 and 168: median 166.5; grandchildren are not taken twice
    assert read("loop.untracked_ms_per_iter", log, w, monkeypatch) == pytest.approx(166.5)
    # what another thread does for the iteration runs beside it and covers none of it
    first = next(r for r in log if r.name == "iter")
    beside = Rec("ckpt.snapshot", first.start + 0.01, first.start + 0.2, 9999, first.id, first.iteration, "ckpt-writer", None)
    assert read("loop.untracked_ms_per_iter", log + [beside], w, monkeypatch) == pytest.approx(166.5)


@pytest.mark.parametrize("name", [
    "env.step_ms", "env.step_wait_ms", "player.sync_mb_per_iter", "loop.stall_ms_per_iter", "loop.untracked_ms_per_iter",
])
def test_an_empty_log_reads_zero_and_a_program_without_one_reads_nothing(name, monkeypatch):
    w = window(4)
    assert read(name, [], w, monkeypatch) == 0.0
    monkeypatch.setattr(spanlog, "records", lambda: None)  # a checkout from before PR 28
    assert load_module("metrics", name).read({"window": w}) is None


def test_open_spans_are_not_in_the_programs_log_and_old_programs_have_none(monkeypatch):
    from sheeprl_tpu.telemetry import SPANS

    SPANS.reset()
    SPANS.iteration(1)
    with SPANS.span("env.step", phase=False):
        pass
    log = spanlog.records()  # `iter` is still open: a run left by WindowClosed
    assert [r.name for r in log] == ["env.step"]
    SPANS.reset()
    monkeypatch.delattr(type(SPANS), "records")
    assert spanlog.records() is None


def test_every_new_reader_has_its_entry_and_every_entry_its_reader():
    bench = json.loads((Path(spanlog.__file__).parent.parent / "BENCHMARK.json").read_text())
    added = {m["name"]: m for m in bench["per_layer"][8:]}
    assert list(added) == ["env.step_ms", "env.step_wait_ms", "player.sync_mb_per_iter", "loop.stall_ms_per_iter", "loop.untracked_ms_per_iter"]
    cells = {w["name"] for w in bench["workloads"]}
    for name, entry in added.items():
        assert set(entry["workloads"]) <= cells and callable(load_module("metrics", name).read)
        assert entry["source"] in ("program_span", "program_counter")
    assert [m["name"] for m in bench["per_layer"][:8]] == [
        "loop.host_ms_per_iter", "player.step_ms", "player.sync_ms", "replay.write_ms", "train.update_ms",
        "anakin.dispatch_ms", "step.mfu_pct", "device.idle_pct",
    ]
