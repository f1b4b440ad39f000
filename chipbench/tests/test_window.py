"""The window's arithmetic on a fake clock: whole-window rates, the p90 over every iteration."""

import pytest

from chipbench.window import Window, median, percentile


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def drive(seconds, iteration_times, steps=4, updates=4, fence=0.0):
    clock = Clock()
    w = Window(seconds, clock)
    w.open()
    closed = False
    for dt in iteration_times:
        clock.now += dt
        due = w.boundary()
        w.add(env_steps=steps, updates=updates)
        if due:
            clock.now += fence  # the closing fence: the device finishes the last iteration
            w.close()
            closed = True
            break
    assert closed, "the iterations did not fill the window"
    return w


def test_rates_are_all_the_work_over_all_the_time():
    w = drive(1.0, [0.125] * 20)
    assert len(w.boundaries) - 1 == 8 and w.elapsed == pytest.approx(1.0)
    m = w.metrics()
    assert m["env_steps_per_s"] == pytest.approx(32.0) and m["updates_per_s"] == pytest.approx(32.0)
    assert m["iter_p90_ms"] == pytest.approx(125.0)


def test_the_window_closes_at_the_first_boundary_at_or_after_its_length_and_counts_the_fence():
    w = drive(1.0, [0.375] * 10, fence=0.0625)
    assert len(w.boundaries) - 1 == 3
    assert w.elapsed == pytest.approx(1.1875)
    assert w.iteration_ms()[-1] == pytest.approx(437.5)
    assert w.metrics()["env_steps_per_s"] == pytest.approx(12 / 1.1875)


def test_a_stall_inside_the_window_lowers_the_rate_and_raises_the_p90():
    steady = drive(2.0, [0.125] * 40)
    stalled = drive(2.0, [0.125] * 4 + [0.5, 0.5] + [0.125] * 40)  # two stalls in 10 iterations: beyond the 90th percentile
    assert len(stalled.boundaries) - 1 == 10
    assert stalled.metrics()["env_steps_per_s"] == pytest.approx(40 / 2.0) and steady.metrics()["env_steps_per_s"] == pytest.approx(32.0)
    assert stalled.metrics()["iter_p90_ms"] == pytest.approx(500.0)
    assert steady.metrics()["iter_p90_ms"] == pytest.approx(125.0)
    assert median(stalled.iteration_ms()) == pytest.approx(125.0)  # which a median would have averaged away


def test_work_outside_the_window_is_not_counted():
    clock = Clock()
    w = Window(1.0, clock)
    w.add(env_steps=99)
    w.open()
    clock.now += 1.0
    assert w.boundary()
    w.add(env_steps=4)
    w.close()
    w.add(env_steps=99)
    assert w.env_steps == 4


def test_percentile_is_nearest_rank():
    assert percentile([5.0, 1.0, 3.0], 90.0) == 5.0
    assert percentile(list(range(1, 101)), 90.0) == 90
    assert percentile([7.0], 90.0) == 7.0
    with pytest.raises(ValueError):
        percentile([], 90.0)
