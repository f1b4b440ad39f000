"""Span nesting / aggregation math, the timer bridge, and the profiler annotations
(no span edge fences the device any more)."""

import pytest

from sheeprl_tpu.telemetry import spans as spans_mod
from sheeprl_tpu.telemetry.spans import SPANS, TIMER_PHASES
from sheeprl_tpu.telemetry.tracer import TRACER


@pytest.fixture()
def clock(monkeypatch):
    """Deterministic span clock: tests advance ``clock['t']`` explicitly."""
    state = {"t": 0.0}
    monkeypatch.setattr(spans_mod, "_now", lambda: state["t"])
    SPANS.roll_window()  # window_start pinned at t=0
    return state


class TestNestingMath:
    def test_exclusive_time_subtracts_children(self, clock):
        outer = SPANS.push("rollout")
        clock["t"] = 1.0
        inner = SPANS.push("queue.wait")
        clock["t"] = 3.0
        SPANS.pop(inner)  # inner: 2s, all exclusive
        clock["t"] = 4.0
        SPANS.pop(outer)  # outer: 4s wall, 2s exclusive
        clock["t"] = 10.0
        bd = SPANS.breakdown()
        assert bd["window_s"] == 10.0
        assert bd["phases"]["queue.wait"]["seconds"] == 2.0
        assert bd["phases"]["rollout"]["seconds"] == 2.0
        assert bd["phases"]["queue.wait"]["frac"] == 0.2
        assert bd["phases"]["rollout"]["frac"] == 0.2
        assert bd["other_frac"] == 0.6

    def test_fractions_sum_to_one(self, clock):
        a = SPANS.push("update.dispatch")
        clock["t"] = 2.5
        SPANS.pop(a)
        clock["t"] = 4.0
        bd = SPANS.breakdown()
        total = sum(p["frac"] for p in bd["phases"].values()) + bd["other_frac"]
        assert total == pytest.approx(1.0, abs=1e-5)

    def test_overlapping_threads_normalize_past_wall(self, clock):
        """Σ exclusive beyond wall time (concurrent threads) still yields
        fractions summing to ~1.0 — normalization uses max(wall, Σ)."""
        # simulate two "threads" by accounting directly: one span of 8s and
        # another of 6s inside a 10s window
        a = SPANS.push("update.dispatch")
        clock["t"] = 8.0
        SPANS.pop(a)
        # second overlapping span: reuse the stack (sequential here, but
        # the accounting sums identically) — total tracked 14s > 10s wall
        clock["t"] = 4.0
        b = SPANS.push("ckpt.snapshot")
        clock["t"] = 10.0
        SPANS.pop(b)
        bd = SPANS.breakdown()
        total = sum(p["frac"] for p in bd["phases"].values()) + bd["other_frac"]
        assert total == pytest.approx(1.0, abs=1e-5)
        assert bd["other_frac"] == 0.0

    def test_leaked_children_close_with_parent(self, clock):
        outer = SPANS.push("rollout")
        clock["t"] = 1.0
        SPANS.push("queue.wait")  # never popped explicitly (e.g. a raise)
        clock["t"] = 3.0
        SPANS.pop(outer)  # unwinds the leaked child too
        bd = SPANS.breakdown()
        assert set(bd["phases"]) == {"rollout", "queue.wait"}
        assert SPANS.depth() == 0

    def test_counts_per_phase(self, clock):
        for _ in range(3):
            tok = SPANS.push("param.broadcast")
            clock["t"] += 1.0
            SPANS.pop(tok)
        assert SPANS.breakdown()["phases"]["param.broadcast"]["count"] == 3

    def test_roll_window_clears(self, clock):
        tok = SPANS.push("rollout")
        clock["t"] = 1.0
        SPANS.pop(tok)
        SPANS.roll_window()
        assert SPANS.breakdown()["phases"] == {}
        assert SPANS.metrics() == {}


class TestDisabled:
    def test_disabled_push_returns_none_and_pop_is_noop(self):
        SPANS.enabled = False
        token = SPANS.push("rollout")
        assert token is None
        SPANS.pop(token)
        assert SPANS.breakdown()["phases"] == {}

    def test_context_manager_disabled(self):
        SPANS.enabled = False
        with SPANS.span("update.dispatch"):
            pass
        assert SPANS.metrics() == {}


class TestTimerBridge:
    def test_timer_names_map_to_phases(self):
        assert TIMER_PHASES["Time/env_interaction_time"] == "rollout"
        assert TIMER_PHASES["Time/train_time"] == "update.dispatch"

    def test_timer_opens_spans_and_ticks_tracer(self):
        from sheeprl_tpu.utils.timer import timer

        ticks_before = TRACER.update_count
        timer.disabled = False
        with timer("Time/train_time"):
            pass
        with timer("Time/env_interaction_time"):
            pass
        metrics = SPANS.metrics()
        assert "Phase/update.dispatch" in metrics
        assert "Phase/rollout" in metrics
        assert TRACER.update_count == ticks_before + 1  # train dispatches only

    def test_timer_bridge_live_at_log_level_zero(self):
        """timer.disabled (metric.log_level=0) must NOT disable spans —
        bench runs rely on phase breakdowns with logging off."""
        from sheeprl_tpu.utils.timer import timer

        timer.to_dict(reset=True)  # drain leftovers from other tests
        timer.disabled = True
        try:
            with timer("Time/train_time"):
                pass
            assert "Phase/update.dispatch" in SPANS.metrics()
            assert timer.to_dict() == {}  # disabled timer recorded nothing
        finally:
            timer.disabled = False


class _Mark:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs its own enter/exit."""

    def __init__(self, log, name, step):
        self.log, self.name, self.step = log, name, step

    def __enter__(self):
        self.log.append(("enter", self.name, self.step))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.step))
        return False


class TestFencing:
    def test_fence_called_only_when_armed(self, monkeypatch):
        """The fence is gone: every push/pop enters and exits one profiler
        annotation of the span's name and never drains the device — with a
        trace window open (``TRACER.active``) as without."""
        from sheeprl_tpu.utils import utils as utils_mod

        syncs, marks = [], []
        monkeypatch.setattr(utils_mod, "device_sync", lambda *a, **k: syncs.append(1))
        monkeypatch.setattr(
            spans_mod, "_annotate", lambda name, span_id, step=None: _Mark(marks, name, step)
        )
        with SPANS.span("rollout"):
            with SPANS.span("env.step", phase=False):
                pass
        assert marks == [
            ("enter", "rollout", None), ("enter", "env.step", None),
            ("exit", "env.step", None), ("exit", "rollout", None),
        ]
        del marks[:]
        monkeypatch.setattr(TRACER, "active", True)  # a trace window is open
        SPANS.iteration(7)
        with SPANS.span("update.dispatch"):
            pass
        SPANS.end_iteration()
        # the iteration is the profiler's step annotation, numbered by the loop
        assert marks == [
            ("enter", "iter", 7), ("enter", "update.dispatch", None),
            ("exit", "update.dispatch", None), ("exit", "iter", 7),
        ]
        assert not syncs
        assert not hasattr(SPANS, "sync") and not hasattr(SPANS, "_fence")

    def test_leaked_children_exit_their_annotations_innermost_first(self, monkeypatch):
        marks = []
        monkeypatch.setattr(
            spans_mod, "_annotate", lambda name, span_id, step=None: _Mark(marks, name, step)
        )
        outer = SPANS.push("rollout")
        SPANS.push("queue.wait")  # leaks (a raise between push and pop)
        SPANS.pop(outer)
        assert [m[:2] for m in marks[2:]] == [("exit", "queue.wait"), ("exit", "rollout")]

    def test_real_annotations_cost_nothing_without_a_profiler_session(self):
        """No session records: the default annotations are jax.profiler's own
        and a span is still just a span."""
        import jax

        mark = spans_mod._annotate("rollout", 1)
        assert isinstance(mark, jax.profiler.TraceAnnotation)
        assert isinstance(spans_mod._annotate("iter", 2, 3), jax.profiler.StepTraceAnnotation)
        with SPANS.span("rollout"):
            pass
        assert SPANS.breakdown()["phases"]["rollout"]["count"] == 1
