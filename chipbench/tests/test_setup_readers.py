"""The seven set-up readers of PR 38 on a hand-made span log: the six times add up to the set-up the log states,
nested compile records are counted once, and nothing is read from a log that holds no ``setup`` record."""

import time
from collections import namedtuple

import pytest

from chipbench import spanlog
from chipbench.harness import load_module
from chipbench.window import Window

Rec = namedtuple("Rec", "name start end id parent iteration thread counts")

PARTS = ["setup.pre_run_s", "setup.compile_s", "setup.build_s", "setup.prefill_s", "setup.warmup_s", "setup.untracked_s"]
READERS = PARTS + ["setup.cache_hit_pct"]


class Clock:
    def __init__(self, now):
        self.now = now

    def __call__(self):
        return self.now


def window(t_open, iterations=4, dt=0.25):
    clock = Clock(t_open)
    w = Window(iterations * dt, clock)
    w.open()
    for _ in range(iterations):
        clock.now += dt
        w.boundary()
    w.close()
    return w


class Log:
    def __init__(self):
        self.records, self._id = [], 0

    def add(self, name, start, seconds, parent=None, iteration=None, counts=None, thread="MainThread"):
        self._id += 1
        self.records.append(Rec(name, start, start + seconds, self._id, parent, iteration, thread, counts))
        return self._id


def token_cell_log(pre_run_ms=6200):
    """``cli.run`` entered at t=100; ``setup`` lasts 40 s; three warm-up iterations; the window opens at t=150.

    children: compose 0.1, register 2, fabric 1, logger 3, agent 10 (a 4 s backend build in it, with a 1 s trace and
    in that trace an inner 0.3 s one), prefill 12 (its program: trace 1, lower 0.5, backend 2.5 read from the cache);
    1.9 s of the root lie under no child, 0.4 s of those a small compile; in the first iteration the steady program is
    traced, lowered and read (6 s of its 7); a pool thread compiles beside the loop and counts for nothing."""
    log = Log()
    root = 1
    log._id = 1  # the root closes after its children: its record comes after theirs, its id before
    log.add("setup.compose", 100.0, 0.1, parent=root)
    log.add("setup.register", 100.1, 2.0, parent=root)
    log.add("setup.fabric", 102.1, 1.0, parent=root)
    log.add("setup.logger", 103.1, 3.0, parent=root)
    log.add("compile.backend", 106.2, 0.4, parent=root, counts={"cache_hit": 1})  # between two children
    agent = log.add("setup.agent", 107.0, 10.0, parent=root)
    log.add("compile.trace", 108.2, 0.3, parent=agent)  # an inner jit, traced inside ...
    log.add("compile.trace", 108.0, 1.0, parent=agent)  # ... the outer one: its event fires later
    log.add("compile.lower", 109.0, 0.5, parent=agent)
    log.add("compile.backend", 109.5, 4.0, parent=agent, counts={"cache_hit": 0})
    log.add("compile.backend", 109.0, 9.0, parent=None, counts={"cache_hit": 0}, thread="sheeprl-compile_0")
    prefill = log.add("setup.prefill", 117.0, 12.0, parent=root)
    log.add("compile.trace", 118.0, 1.0, parent=prefill)
    log.add("compile.lower", 119.0, 0.5, parent=prefill)
    log.add("compile.backend", 119.5, 2.5, parent=prefill, counts={"cache_hit": 1})
    log.records.append(Rec("setup", 100.0, 140.0, root, None, None, "MainThread", None if pre_run_ms is None else {"pre_run_ms": pre_run_ms}))
    first = log.add("iter", 140.0, 7.0, iteration=1)
    exe = log.add("exec.ppo_recurrent.anakin_phase", 140.1, 6.8, parent=first, iteration=1)
    log.add("compile.trace", 140.2, 2.0, parent=exe, iteration=1)
    log.add("compile.lower", 142.2, 1.0, parent=exe, iteration=1)
    log.add("compile.backend", 143.2, 3.0, parent=exe, iteration=1, counts={"cache_hit": 1})
    log.add("iter", 147.0, 1.5, iteration=2)
    log.add("iter", 148.5, 1.5, iteration=3)
    w = window(150.0)
    log.add("iter", 150.0, 0.25, iteration=4)
    log.add("compile.backend", 150.1, 0.1, iteration=4, counts={"cache_hit": 0})  # inside the window: not set-up's
    return log.records, w


def read(name, log, w, monkeypatch):
    monkeypatch.setattr(spanlog, "records", lambda: log)
    return load_module("metrics", name).read({"window": w})


def test_each_part_is_what_the_log_states(monkeypatch):
    log, w = token_cell_log()
    got = {name: read(name, log, w, monkeypatch) for name in READERS}
    assert got["setup.pre_run_s"] == pytest.approx(6.2)
    # 0.4 between children; 1 + 0.5 + 4 in the agent (the inner trace counted once); 4 in the prefill; 6 in the first iteration
    assert got["setup.compile_s"] == pytest.approx(0.4 + 5.5 + 4.0 + 6.0)
    assert got["setup.build_s"] == pytest.approx(0.1 + 2.0 + 1.0 + 3.0 + (10.0 - 5.5))
    assert got["setup.prefill_s"] == pytest.approx(12.0 - 4.0)
    assert got["setup.warmup_s"] == pytest.approx(10.0 - 6.0)
    assert got["setup.untracked_s"] == pytest.approx(40.0 - 28.1 - 0.4)
    # four backend compiles before the window on the loop's thread and one on the pool's; three were cache reads
    assert got["setup.cache_hit_pct"] == pytest.approx(100.0 * 3 / 5)


def test_the_six_times_add_up_to_the_set_up(monkeypatch):
    log, w = token_cell_log()
    # process start 6.2 s before cli.run's entry at t=100; the window opens at t=150
    assert sum(read(name, log, w, monkeypatch) for name in PARTS) == pytest.approx(6.2 + 50.0)
    shifted = [r._replace(start=r.start + 0.7, end=r.end + 0.9) if r.name.startswith("compile.") else r for r in log]
    assert sum(read(name, shifted, w, monkeypatch) for name in PARTS) == pytest.approx(6.2 + 50.0)  # wherever compiles fall


def test_a_compile_that_straddles_the_root_s_end_is_split_not_counted_twice(monkeypatch):
    log, w = token_cell_log()
    log = log + [Rec("compile.backend", 139.0, 141.0, 999, None, None, "MainThread", {"cache_hit": 0})]
    assert sum(read(name, log, w, monkeypatch) for name in PARTS) == pytest.approx(6.2 + 50.0)
    assert read("setup.untracked_s", log, w, monkeypatch) == pytest.approx(40.0 - 28.1 - 0.4 - 1.0)
    # of its second half, 0.2 s lie before the first iteration's own compile begins and 0.8 s inside it
    assert read("setup.warmup_s", log, w, monkeypatch) == pytest.approx(4.0 - 0.2)


def test_no_start_time_leaves_the_one_metric_out(monkeypatch):
    log, w = token_cell_log(pre_run_ms=None)
    assert read("setup.pre_run_s", log, w, monkeypatch) is None
    assert read("setup.compile_s", log, w, monkeypatch) == pytest.approx(15.9)


def test_a_cell_without_prefill_reads_nought_there_and_the_rest_as_it_is(monkeypatch):
    log, w = token_cell_log()
    log = [r for r in log if r.name != "setup.prefill" and r.parent != 13]
    assert read("setup.prefill_s", log, w, monkeypatch) == 0.0
    assert read("setup.untracked_s", log, w, monkeypatch) == pytest.approx(40.0 - 16.1 - 0.4)


@pytest.mark.parametrize("name", READERS)
def test_nothing_is_read_without_a_setup_record(name, monkeypatch):
    log, w = token_cell_log()
    no_root = [r for r in log if r.name != "setup"]  # the parent commit, or a log that has wrapped
    assert read(name, no_root, w, monkeypatch) is None
    assert read(name, [], w, monkeypatch) is None
    later = [r._replace(start=r.start + 100.0, end=r.end + 100.0) for r in log]  # a root that closed after this window opened
    assert read(name, later, w, monkeypatch) is None
    monkeypatch.setattr(spanlog, "records", lambda: None)  # a checkout from before PR 28
    assert load_module("metrics", name).read({"window": w}) is None


def test_the_run_s_own_root_is_the_newest_before_the_window(monkeypatch):
    log, w = token_cell_log()
    older = [r._replace(start=r.start - 80.0, end=r.end - 80.0, id=r.id + 1000, parent=None if r.parent is None else r.parent + 1000,
                        counts={"pre_run_ms": 99000} if r.name == "setup" else r.counts) for r in log]  # a run before it, in this process
    assert read("setup.pre_run_s", older + log, w, monkeypatch) == pytest.approx(6.2)
    assert read("setup.build_s", older + log, w, monkeypatch) == pytest.approx(10.6)


def test_the_readers_take_the_program_s_own_log():
    from sheeprl_tpu.telemetry import SPANS

    SPANS.reset()
    SPANS.begin_setup()
    with SPANS.setup_span("setup.agent"):
        time.sleep(0.005)  # a compile that began inside the span
        SPANS.closed("compile.backend", 0.004, {"cache_hit": 1})
    SPANS.iteration(1)
    w = window(time.perf_counter())
    SPANS.end_iteration()
    parts = load_module("metrics", "setup.compile_s").account({"window": w})
    SPANS.reset()
    assert parts["compile_s"] == pytest.approx(0.004, abs=1e-3) and parts["prefill_s"] == 0.0
    assert parts["pre_run_s"] is None or parts["pre_run_s"] > 0
    assert load_module("metrics", "setup.cache_hit_pct").read({"window": w}) is None  # the log is empty again
