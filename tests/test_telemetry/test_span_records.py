"""The span log (one record per closed span), the iteration frame, and what
stays top-level under it: tracer ticks, /healthz liveness, recorder events."""

import threading
import time

import pytest

from sheeprl_tpu.telemetry import COMPILE_MONITOR, RECORDER, SPANS, TRACER
from sheeprl_tpu.telemetry import spans as spans_mod
from sheeprl_tpu.telemetry.spans import ITER, RECORD_CAPACITY, SpanRecord


def by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


class TestRecords:
    def test_record_fields_on_the_perf_counter_clock(self):
        t0 = time.perf_counter()
        with SPANS.span("player.sync", phase=False, bytes=100) as token:
            token.count(bytes=28)
        t1 = time.perf_counter()
        (rec,) = SPANS.records()
        assert isinstance(rec, SpanRecord)
        assert rec.name == "player.sync" and t0 <= rec.start <= rec.end <= t1
        assert rec.parent is None and rec.iteration is None
        assert rec.thread == threading.current_thread().name
        assert rec.counts == {"bytes": 128}

    def test_parent_and_iteration_run_down_the_stack(self):
        SPANS.iteration(41)
        with SPANS.span("rollout"):
            with SPANS.span("env.step", phase=False):
                pass
        with SPANS.span("update.dispatch"):
            pass
        SPANS.iteration(42)  # closes 41
        with SPANS.span("rollout"):
            pass
        SPANS.end_iteration()
        recs = by_name(SPANS.records())
        it41, it42 = recs[ITER]
        assert (it41.iteration, it42.iteration) == (41, 42) and it41.parent is None
        assert it41.end <= it42.start
        roll41, roll42 = recs["rollout"]
        assert roll41.parent == it41.id and roll42.parent == it42.id
        assert recs["env.step"][0].parent == roll41.id
        assert recs["update.dispatch"][0].parent == it41.id
        assert {r.iteration for r in SPANS.records() if r.id != it42.id and r is not roll42} == {41}
        assert roll42.iteration == 42
        assert len({r.id for r in SPANS.records()}) == len(SPANS.records())

    def test_a_worker_thread_takes_the_span_that_caused_its_work(self):
        SPANS.iteration(9)
        with SPANS.span("ckpt.save", phase=False):
            cause = SPANS.current()
        SPANS.end_iteration()

        def work():
            token = SPANS.push("ckpt.snapshot", cause=cause)
            SPANS.pop(token)

        t = threading.Thread(target=work, name="ckpt-writer-test")
        t.start()
        t.join()
        recs = by_name(SPANS.records())
        snap, save = recs["ckpt.snapshot"][0], recs["ckpt.save"][0]
        assert snap.parent == save.id and snap.iteration == 9
        assert snap.thread == "ckpt-writer-test" and save.thread != snap.thread

    def test_the_checkpoint_writer_thread_names_the_save_that_queued_it(self):
        from sheeprl_tpu.checkpoint.writer import AsyncCheckpointWriter

        writer = AsyncCheckpointWriter(queue_size=2, hang_warn_s=0)
        try:
            SPANS.iteration(5)
            with SPANS.span("ckpt.save", phase=False):
                writer.submit(lambda: 7)
            SPANS.end_iteration()
            writer.flush(10.0)
        finally:
            writer.close(10.0)
        recs = by_name(SPANS.records())
        snap, save = recs["ckpt.snapshot"][0], recs["ckpt.save"][0]
        assert snap.parent == save.id and snap.iteration == 5
        assert snap.thread == "ckpt-writer"

    def test_roll_window_keeps_the_log(self):
        with SPANS.span("rollout"):
            pass
        SPANS.roll_window()
        assert SPANS.breakdown()["phases"] == {}
        with SPANS.span("update.dispatch"):
            pass
        assert [r.name for r in SPANS.records()] == ["rollout", "update.dispatch"]

    def test_the_log_is_a_bounded_ring(self):
        assert RECORD_CAPACITY >= 32768
        assert SPANS._records.maxlen == RECORD_CAPACITY
        tiny = spans_mod.SpanTracker()
        tiny._records = type(tiny._records)(maxlen=4)
        for i in range(6):
            tiny.pop(tiny.push(f"s{i}"))
        assert [r.name for r in tiny.records()] == ["s2", "s3", "s4", "s5"]

    def test_disabled_spans_keep_no_records(self):
        SPANS.enabled = False
        SPANS.iteration(1)
        with SPANS.span("rollout"):
            pass
        SPANS.end_iteration()
        assert SPANS.records() == [] and SPANS.current() is None


class TestOpenSpans:
    def test_a_baseexception_leaves_open_spans_out_and_the_log_readable(self):
        class Closed(BaseException):
            pass

        def loop():
            for update in (1, 2, 3):
                SPANS.iteration(update)
                SPANS.push("rollout")  # as the timer does: no try/finally of its own
                if update == 3:
                    raise Closed()
                SPANS.pop(SPANS.current())

        with pytest.raises(Closed):
            loop()
        recs = SPANS.records()  # readable, and holds closed spans only
        assert [(r.name, r.iteration) for r in recs] == [
            ("rollout", 1), (ITER, 1), ("rollout", 2), (ITER, 2),
        ]
        assert SPANS.depth() == 2  # iter 3 and its rollout never closed
        # cli.run's finally (telemetry.shutdown_run) closes the frame; the log grows, nothing is cleared
        from sheeprl_tpu import telemetry

        telemetry.shutdown_run()
        assert SPANS.depth() == 0
        assert [(r.name, r.iteration) for r in SPANS.records()][:4] == [(r.name, r.iteration) for r in recs]
        assert (ITER, 3) in [(r.name, r.iteration) for r in SPANS.records()]

    def test_the_next_run_starts_from_a_clean_frame(self):
        SPANS.iteration(1)
        SPANS.push("rollout")
        SPANS.iteration(2)  # a loop that lost its rollout's pop: the frame closes what leaked
        SPANS.end_iteration()
        assert SPANS.depth() == 0
        assert [r.name for r in SPANS.records()] == ["rollout", ITER, ITER]


class TestTopLevelUnderIter:
    def test_tracer_ticks_and_liveness_count_dispatches_under_an_iteration(self):
        ticks = TRACER.update_count
        assert SPANS.updates_done == 0 and SPANS.last_update_age_s() is None
        for update in (1, 2):
            SPANS.iteration(update)
            with SPANS.span("rollout"):
                pass
            with SPANS.span("update.dispatch"):
                pass
        SPANS.end_iteration()
        assert TRACER.update_count == ticks + 2
        assert SPANS.updates_done == 2
        assert 0.0 <= SPANS.last_update_age_s() < 5.0

    def test_a_nested_update_dispatch_still_counts_for_nothing(self):
        ticks = TRACER.update_count
        SPANS.iteration(1)
        with SPANS.span("rollout"):
            with SPANS.span("env.step", phase=False):  # a boundary between them hides no phase
                with SPANS.span("update.dispatch"):
                    pass
        SPANS.end_iteration()
        assert TRACER.update_count == ticks and SPANS.updates_done == 0

    def test_boundaries_make_no_phase_less_top_level(self):
        ticks = TRACER.update_count
        SPANS.iteration(1)
        with SPANS.span("ckpt.save", phase=False):
            with SPANS.span("update.dispatch"):
                pass
        SPANS.end_iteration()
        assert TRACER.update_count == ticks + 1 and SPANS.updates_done == 1

    def test_trace_at_numbering_is_the_dispatch_count_under_iterations(self):
        started, stopped = [], []
        from sheeprl_tpu.telemetry.tracer import TraceScheduler

        sched = TraceScheduler(start_fn=started.append, stop_fn=lambda: stopped.append(1))
        sched.configure({"trace_at": [2], "trace_updates": 1, "trace_dir": "/tmp/t"}, None)
        orig = spans_mod.TRACER
        spans_mod.TRACER = sched
        try:
            for update in (1, 2, 3):
                SPANS.iteration(update)
                with SPANS.span("update.dispatch"):
                    assert sched.active == (update == 2)
            SPANS.end_iteration()
        finally:
            spans_mod.TRACER = orig
        assert started == ["/tmp/t/update_000002"] and stopped == [1]

    def test_recorder_events_are_those_of_before(self):
        """Top-level edges of the phases are recorder events; the iteration
        frame and the boundaries (``phase=False``) are not, wherever they
        are opened."""
        RECORDER.clear()
        SPANS.iteration(1)
        with SPANS.span("rollout"):
            with SPANS.span("exec.dreamer_v3.player_step", phase=False):
                pass
            with SPANS.span("replay.write"):
                pass
        with SPANS.span("update.dispatch"):
            pass
        with SPANS.span("log.flush", phase=False):
            pass
        with SPANS.span("health.poll", phase=False):
            pass
        SPANS.end_iteration()
        with SPANS.span("exec.serve.policy", phase=False):  # outside any loop: a served request
            pass
        with SPANS.span("replay.write"):  # outside any phase, as before: an event
            pass
        names = [e["name"] for e in RECORDER.snapshot() if e["kind"] == "span"]
        assert names == ["rollout", "update.dispatch", "replay.write"]

    def test_phases_keep_the_time_of_the_boundaries_under_them(self):
        """``Phase/*`` is what it was before the boundaries existed: their
        time stays with the phase they run under, the frame's own with
        ``other``, and no boundary is a ``Phase/*`` key."""
        SPANS.roll_window()
        SPANS.iteration(1)
        with SPANS.span("rollout"):
            with SPANS.span("env.step", phase=False):
                with SPANS.span("exec.player_step", phase=False):
                    time.sleep(0.002)
            with SPANS.span("replay.write"):  # a phase under a phase: taken out of rollout, as before
                time.sleep(0.002)
        with SPANS.span("log.flush", phase=False):
            time.sleep(0.002)  # under no phase: other
        SPANS.end_iteration()
        bd = SPANS.breakdown()
        assert set(bd["phases"]) == {"rollout", "replay.write"}
        assert bd["phases"]["rollout"]["seconds"] >= 0.0015  # the boundaries' 2 ms
        assert bd["phases"]["rollout"]["seconds"] < bd["window_s"] - 0.003  # less replay.write's and the flush's
        assert bd["other_frac"] * bd["window_s"] >= 0.0015
        assert set(SPANS.metrics()) == {"Phase/rollout", "Phase/replay.write", "Phase/other"}
        total = sum(p["frac"] for p in bd["phases"].values()) + bd["other_frac"]
        assert total == pytest.approx(1.0, abs=1e-4)


class TestCompileEvents:
    def test_jax_compile_events_are_counted_and_put_down_to_the_open_span(self):
        import jax
        import jax.numpy as jnp

        COMPILE_MONITOR.install()
        COMPILE_MONITOR.install()  # idempotent: one listener
        salt = float(time.time() % 1000.0)  # a program no other test has compiled
        x = jnp.ones(3)  # made outside: jnp.ones is a program of its own
        before, _ = COMPILE_MONITOR.backend_totals()
        RECORDER.clear()
        SPANS.iteration(12)
        with SPANS.span("replay.write"):
            jax.jit(lambda x: x * salt + 3.0)(x).block_until_ready()
        SPANS.end_iteration()
        after, seconds = COMPILE_MONITOR.backend_totals()
        assert after == before + 1 and seconds > 0.0
        events = [e for e in RECORDER.snapshot() if e["kind"] == "compile.backend"]
        assert len(events) == 1
        assert events[0]["span"] == "replay.write" and events[0]["iteration"] == 12
        metrics = COMPILE_MONITOR.metrics()
        assert metrics["Compile/backend_compiles"] == float(after)
        assert metrics["Compile/backend_compile_time_s"] >= 0.0
        # logged while it moves only: after a rolling flush a steady run pays no scalar for it
        COMPILE_MONITOR.roll()
        assert not any(k.startswith("Compile/backend") for k in COMPILE_MONITOR.metrics())
        jax.jit(lambda x: x * salt - 5.0)(x).block_until_ready()
        assert COMPILE_MONITOR.metrics()["Compile/backend_compiles"] == float(after + 1)

    def test_a_compile_outside_any_span_names_none(self):
        import jax
        import jax.numpy as jnp

        COMPILE_MONITOR.install()
        salt = float(time.time() % 1000.0) + 0.5
        x = jnp.ones(2)
        RECORDER.clear()
        jax.jit(lambda x: x * salt - 1.0)(x).block_until_ready()
        (event,) = [e for e in RECORDER.snapshot() if e["kind"] == "compile.backend"]
        assert event["span"] is None and event["iteration"] is None


def test_device_scope_names_are_stable_strings():
    from chipbench.scopes import SCOPES  # the one list of them

    assert len(set(SCOPES)) == len(SCOPES) == 20
    assert all(" " not in s and "/" not in s and "(" not in s for s in SCOPES)
