"""Set-up in the program's own spans (PR 38): the root ``setup`` from ``cli.run``'s
entry to the first ``iter``, its ``setup.*`` children opened where the work is,
every compile as a closed ``compile.*`` record from JAX's own events, and the
view of what is open now.  Three CPU dry runs (the three mains the benchmark's
cells run) and the tracker's new calls one by one."""

import collections
import json
import threading
import time

import jax  # noqa: F401  (before any begin_setup: the first to import it also writes `setup.import`)
import pytest

from sheeprl_tpu.cli import run
from sheeprl_tpu.telemetry import COMPILE_MONITOR, RECORDER, SPANS
from sheeprl_tpu.telemetry.monitors import BACKEND_COMPILE_EVENT, COMPILE_RECORDS
from sheeprl_tpu.telemetry.spans import ITER, SETUP

COMMON = [
    "env.capture_video=False", "fabric.devices=1", "fabric.accelerator=cpu",
    "buffer.memmap=False", "metric.log_level=1", "metric.log_every=1",
    "algo.run_test=False", "print_config=False",
]

RUNS = {
    "anakin": [
        "exp=ppo", "env=jax_cartpole", "env.num_envs=2",
        "algo.rollout_steps=8", "algo.per_rank_batch_size=8", "algo.update_epochs=1",
        "algo.total_steps=48",  # 3 fused dispatches
        "algo.mlp_keys.encoder=[state]", "algo.max_recompiles=1",
        "checkpoint.every=32", "checkpoint.save_last=False",
    ],
    "dv3": [
        "exp=dreamer_v3", "env=dummy", "env.id=discrete_dummy", "dry_run=True",
        "env.num_envs=2", "env.sync_env=True", "buffer.size=512", "buffer.device=True",
        "checkpoint.every=0", "checkpoint.save_last=False",
        "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[state]",
        "algo.horizon=4", "algo.dense_units=16", "algo.mlp_layers=1",
        "algo.world_model.encoder.cnn_channels_multiplier=4",
        "algo.world_model.recurrent_model.recurrent_state_size=16",
        "algo.world_model.transition_model.hidden_size=16",
        "algo.world_model.representation_model.hidden_size=16",
        "algo.world_model.discrete_size=4", "algo.world_model.stochastic_size=4",
        "algo.per_rank_batch_size=2", "algo.per_rank_sequence_length=8",
    ],
    "tokens": [
        "exp=ppo_tokens", "algo/decoder@algo.decoder=tiny", "env.wrapper.vocab_size=64",
        "env.wrapper.prompt_min=2", "env.wrapper.prompt_max=4", "env.wrapper.len_min=16",
        "env.wrapper.len_max=32", "env.num_envs=4", "algo.rollout_steps=8",
        "algo.per_rank_batch_size=16", "algo.total_steps=64",  # 2 fused dispatches
        "fabric.precision=32-true", "checkpoint.every=0", "checkpoint.save_last=False",
    ],
}

#: the children every run opens, and what each main adds
EVERY_RUN = {"setup.compose", "setup.register", "setup.fabric", "setup.logger", "setup.env", "setup.agent", "setup.optimizer"}
OWN = {"anakin": set(), "dv3": {"setup.replay"}, "tokens": {"setup.prefill"}}
CHILDREN = EVERY_RUN | {"setup.replay", "setup.resume", "setup.prefill", "setup.import"}

#: the root's time that no child and no compile covers, as a share of the root, stays under this
SELF_SHARE = 0.15


def union_s(intervals):
    covered, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            covered += end - max(start, reach)
            reach = end
    return covered


@pytest.fixture(scope="module", params=list(RUNS))
def dry_run(request, tmp_path_factory):
    """(which main, its closed spans) of one CLI dry run."""
    SPANS.reset()
    run(RUNS[request.param] + COMMON + [f"log_dir={tmp_path_factory.mktemp(request.param)}"])
    return request.param, SPANS.records()


class TestDryRuns:
    def test_one_setup_record_closed_by_the_first_iter_with_its_children(self, dry_run):
        which, records = dry_run
        (root,) = [r for r in records if r.name == SETUP]
        first = min((r for r in records if r.name == ITER), key=lambda r: r.start)
        assert root.parent is None and root.iteration is None and root.thread == first.thread
        assert root.end <= first.start and first.start - root.end < 0.05  # closed by that very call
        assert root.counts["pre_run_ms"] > 0  # the process was older than cli.run's entry
        children = [r for r in records if r.name.startswith("setup.")]
        assert {r.name for r in children} <= CHILDREN
        assert EVERY_RUN | OWN[which] <= {r.name for r in children}
        assert all(r.parent == root.id and r.iteration is None and r.thread == root.thread for r in children)
        assert all(root.start <= r.start and r.end <= root.end for r in children)
        assert all(a.end <= b.start for a, b in zip(children, children[1:]))  # one after another
        # what no child and no compile owns: where it is large, a layer of set-up is missing its span
        compiles = [(r.start, r.end) for r in records if r.name.startswith("compile.") and r.thread == root.thread]
        outside = [
            (max(s, a), min(e, b))
            for a, b in zip([root.start] + [c.end for c in children], [c.start for c in children] + [root.end])
            for s, e in compiles if e > a and s < b
        ]
        own = (root.end - root.start) - sum(r.end - r.start for r in children) - union_s(outside)
        assert own / (root.end - root.start) < SELF_SHARE

    def test_every_compile_is_in_the_log_under_the_span_open_on_its_thread(self, dry_run):
        which, records = dry_run
        by_id = {r.id: r for r in records}
        compiles = [r for r in records if r.name.startswith("compile.")]
        assert {r.name for r in compiles} == set(COMPILE_RECORDS.values())
        # where the steady program is built: at its first dispatch, or (DV3) by the probe compile that sizes the ring
        built_under = {"anakin": "exec.ppo.anakin_phase", "dv3": "setup.replay", "tokens": "exec.ppo_recurrent.anakin_phase"}[which]
        assert any(by_id[r.parent].name == built_under for r in compiles if r.name == "compile.backend" and r.parent in by_id)
        for r in compiles:
            assert r.counts is None or set(r.counts) == {"cache_hit"} and r.name == "compile.backend"
            if r.parent is None:
                continue
            parent = by_id[r.parent]  # the span that was open when the event fired: it holds the event's end
            assert parent.thread == r.thread and parent.iteration == r.iteration
            assert parent.start <= r.end <= parent.end
            assert not parent.name.startswith("compile.")  # closed records are nobody's parent
        backends = [r for r in compiles if r.name == "compile.backend"]
        assert all(r.counts["cache_hit"] in (0, 1) for r in backends)

    def test_a_steady_iteration_closes_the_records_it_did(self, dry_run):
        """Nothing of set-up or of the compile records reaches the hot path: an iteration that builds no
        program closes the spans the parent commit's closed (counted there on the same runs)."""
        which, records = dry_run
        by_iteration = collections.defaultdict(collections.Counter)
        for r in records:
            by_iteration[r.iteration][r.name] += 1
        expected = {
            "anakin": (3, {"iter": 1, "update.dispatch": 1, "exec.ppo.anakin_phase": 1, "stats.pull": 1, "log.flush": 1}),
            "dv3": (19, {"iter": 1, "rollout": 1, "env.step": 1, "replay.write": 1, "exec.dreamer_v3.player_step": 1, "log.flush": 1}),
            "tokens": (2, {"iter": 1, "update.dispatch": 1, "exec.ppo_recurrent.anakin_phase": 1, "stats.pull": 1, "log.flush": 1}),
        }[which]
        assert dict(by_iteration[expected[0]]) == expected[1]

    def test_phase_keys_are_the_phases(self, dry_run):
        # set-up and compile records are boundaries and closed records: no part of Phase/*
        assert set(SPANS.breakdown()["phases"]) <= {"rollout", "update.dispatch", "replay.write", "ckpt.snapshot"}


class TestSetupRoot:
    def test_children_announce_themselves_and_outside_set_up_they_are_nothing(self, capsys):
        with SPANS.setup_span("setup.env") as token:  # a shared constructor called by evaluation, or mid-run
            assert token is None
        assert SPANS.records() == [] and capsys.readouterr().err == ""
        SPANS.begin_setup()
        with SPANS.setup_span("setup.agent"):
            time.sleep(0.002)
        SPANS.iteration(1)
        SPANS.end_iteration()
        names = [r.name for r in SPANS.records()]
        assert names == ["setup.agent", "setup", "iter"]
        lines = capsys.readouterr().err.splitlines()
        assert [line.split()[0] for line in lines] == ["setup.agent", "setup"] and all(line.endswith(" s") for line in lines)
        events = [e for e in RECORDER.snapshot() if e["kind"] == "setup"]
        assert [e["name"] for e in events] == ["setup.agent", "setup"] and events[0]["seconds"] >= 0.002
        with SPANS.setup_span("setup.env") as token:  # set-up is over
            assert token is None

    def test_the_first_import_of_jax_is_a_child_of_its_own(self, monkeypatch):
        import sys

        monkeypatch.delitem(sys.modules, "jax")  # `python -m sheeprl_tpu`: cli.run is the first to need it
        SPANS.begin_setup()
        SPANS.end_setup()
        first, root = SPANS.records()
        assert (first.name, root.name) == ("setup.import", SETUP) and first.parent == root.id
        assert root.start == pytest.approx(first.start, abs=1e-4) and first.end <= root.end  # the root begins at cli.run's entry, not after the import

    def test_a_loop_without_the_iter_frame_ends_set_up_at_its_first_phase(self):
        SPANS.begin_setup()
        with SPANS.span("env.step", phase=False):  # a boundary does not: the envs' first reset is set-up
            pass
        with SPANS.span("rollout"):
            pass
        root, rollout = (next(r for r in SPANS.records() if r.name == n) for n in (SETUP, "rollout"))
        assert root.end <= rollout.start and rollout.parent is None
        assert [e["name"] for e in RECORDER.snapshot() if e["kind"] == "span"] == ["rollout"]  # top-level, as before

    def test_a_run_that_turns_spans_off_is_left_with_no_half_open_root(self):
        with SPANS.span("exec.serve.policy", phase=False):
            pass  # an older record: it stays
        SPANS.enabled = False  # the run before this one had them off
        SPANS.begin_setup()  # a new run starts with the default knobs ...
        with SPANS.setup_span("setup.compose"):
            pass
        with SPANS.setup_span("setup.logger"):
            SPANS.configure({"enabled": False})  # ... until setup_run applies its own, inside get_logger
        assert SPANS.depth() == 0 and SPANS.open_spans() == []
        assert [r.name for r in SPANS.records()] == ["exec.serve.policy"]
        SPANS.iteration(1)
        SPANS.end_setup()
        assert SPANS.depth() == 0 and len(SPANS.records()) == 1

    def test_shutdown_closes_a_root_whose_run_never_reached_a_loop(self):
        from sheeprl_tpu import telemetry

        SPANS.begin_setup()
        with pytest.raises(ValueError):
            with SPANS.setup_span("setup.agent"):
                raise ValueError("no such model")
        assert [s["name"] for s in SPANS.open_spans()] == [SETUP]
        telemetry.shutdown_run()
        assert [r.name for r in SPANS.records()] == ["setup.agent", SETUP] and SPANS.open_spans() == []

    def test_phase_fractions_are_those_of_a_run_without_the_boundaries(self):
        SPANS.roll_window()
        SPANS.begin_setup()
        with SPANS.setup_span("setup.agent"):
            SPANS.closed("compile.backend", 0.001, {"cache_hit": 1})
            time.sleep(0.002)
        SPANS.iteration(1)
        with SPANS.span("rollout"):
            time.sleep(0.002)
        SPANS.end_iteration()
        bd = SPANS.breakdown()
        assert set(bd["phases"]) == {"rollout"} and set(SPANS.metrics()) == {"Phase/rollout", "Phase/other"}
        assert bd["other_frac"] * bd["window_s"] >= 0.0015  # set-up's time is `other`, as host time without a span was
        assert sum(p["frac"] for p in bd["phases"].values()) + bd["other_frac"] == pytest.approx(1.0, abs=1e-4)


class TestClosedRecords:
    def test_a_closed_record_ends_now_and_takes_the_open_span_as_its_parent(self):
        SPANS.iteration(7)
        with SPANS.span("update.dispatch") as outer:
            t = time.perf_counter()
            SPANS.closed("compile.trace", 0.25)
            SPANS.closed("compile.backend", 1.5, {"cache_hit": 0})
        SPANS.end_iteration()
        trace, backend = SPANS.records()[:2]
        assert (trace.name, backend.name) == ("compile.trace", "compile.backend")
        assert trace.end - trace.start == pytest.approx(0.25) and abs(trace.end - t) < 0.05
        assert backend.start == pytest.approx(backend.end - 1.5) and backend.counts == {"cache_hit": 0}
        assert trace.parent == backend.parent == outer.id and trace.iteration == 7 and trace.thread == "MainThread"
        assert set(SPANS.breakdown()["phases"]) == {"update.dispatch"}
        SPANS.enabled = False
        SPANS.closed("compile.lower", 1.0)
        assert len(SPANS.records()) == 4

    def test_the_listener_writes_one_record_an_event_and_a_line_for_a_long_build(self, capsys):
        SPANS.begin_setup()
        with SPANS.setup_span("setup.agent"):
            COMPILE_MONITOR._on_jax_event("/jax/core/compile/jaxpr_trace_duration", 0.0004, fun_name="inner")  # under the floor
            COMPILE_MONITOR._on_jax_event("/jax/core/compile/jaxpr_trace_duration", 0.3, fun_name="f")
            COMPILE_MONITOR._on_jax_event("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.2, fun_name="jit_f")
            COMPILE_MONITOR._on_jax_mark("/jax/compilation_cache/cache_hits")
            COMPILE_MONITOR._on_jax_event(BACKEND_COMPILE_EVENT, 2.5, fun_name="jit_f")
            COMPILE_MONITOR._on_jax_event(BACKEND_COMPILE_EVENT, 0.5, fun_name="jit_g")  # built: no hit came before it
            COMPILE_MONITOR._on_jax_event("/jax/some/other_duration", 9.0)
        compiles = [r for r in SPANS.records() if r.name.startswith("compile.")]
        assert [(r.name, r.counts) for r in compiles] == [
            ("compile.trace", None), ("compile.lower", None),
            ("compile.backend", {"cache_hit": 1}), ("compile.backend", {"cache_hit": 0}),
        ]
        events = [e for e in RECORDER.snapshot() if e["kind"] == "compile.backend"]
        assert [(e["span"], e["seconds"], e["cache_hit"]) for e in events] == [("setup.agent", 2.5, 1), ("setup.agent", 0.5, 0)]
        assert not [e for e in RECORDER.snapshot() if e["kind"] == "compile"]  # one event a compile
        lines = [line for line in capsys.readouterr().err.splitlines() if line.startswith("compile.")]
        assert lines == ["compile.backend setup.agent 2.5 s hit"]  # a second or more: the build that is watched

    def test_cache_hit_is_0_on_an_empty_cache_and_1_when_the_program_is_built_again(self, tmp_path):
        import jax
        import jax.numpy as jnp
        from jax.experimental.compilation_cache import compilation_cache as cc

        COMPILE_MONITOR.install()
        saved = {k: getattr(jax.config, k) for k in (
            "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes")}
        salt = float(time.time() % 1000.0)
        x = jnp.ones(4)

        def program(x):
            return jnp.tanh(x * salt) + 2.0

        def build():
            with SPANS.span("exec.test.program", phase=False):
                jax.jit(program)(x).block_until_ready()
            return [r for r in SPANS.records() if r.name == "compile.backend"][-1]

        try:
            jax.config.update("jax_compilation_cache_dir", str(tmp_path))
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
            cc.reset_cache()
            cold = build()
            jax.clear_caches()
            warm = build()
        finally:
            for k, v in saved.items():
                jax.config.update(k, v)
            cc.reset_cache()
        assert cold.counts == {"cache_hit": 0} and warm.counts == {"cache_hit": 1}
        by_id = {r.id: r for r in SPANS.records()}
        assert by_id[cold.parent].name == by_id[warm.parent].name == "exec.test.program"


class TestOpenSpanView:
    def test_it_names_what_has_not_closed_on_any_thread_oldest_first(self):
        assert SPANS.open_spans() == []
        entered, leave = threading.Event(), threading.Event()

        def worker():
            with SPANS.span("ckpt.snapshot"):
                entered.set()
                leave.wait(5)

        SPANS.begin_setup()
        thread = threading.Thread(target=worker, name="writer")
        with SPANS.setup_span("setup.replay"):
            thread.start()
            assert entered.wait(5)
            time.sleep(0.002)
            view = SPANS.open_spans()
            assert [(s["name"], s["thread"]) for s in view] == [
                (SETUP, "MainThread"), ("setup.replay", "MainThread"), ("ckpt.snapshot", "writer")]
            assert view[0]["age_s"] >= view[1]["age_s"] >= view[2]["age_s"] >= 0.002
            assert not any(r.name in (SETUP, "setup.replay") for r in SPANS.records())  # the log holds closed spans only
            leave.set()
            thread.join()
        assert [s["name"] for s in SPANS.open_spans()] == [SETUP]

    def test_the_postmortem_and_the_endpoint_carry_it(self, tmp_path):
        import urllib.request

        from sheeprl_tpu.telemetry.introspect import IntrospectionServer

        SPANS.begin_setup()
        with SPANS.setup_span("setup.agent"):
            COMPILE_MONITOR._on_jax_event(BACKEND_COMPILE_EVENT, 0.2, fun_name="jit_init")
            path = RECORDER.dump("watchdog", path=str(tmp_path / "postmortem.json"))
            with IntrospectionServer(port=0) as server:
                with urllib.request.urlopen(server.url + "/v1/phase", timeout=5) as resp:
                    phase = json.loads(resp.read())
        doc = json.loads(open(path).read())
        assert [s["name"] for s in doc["open_spans"]] == [s["name"] for s in phase["open"]] == [SETUP, "setup.agent"]
        assert doc["events"][-1]["kind"] == "compile.backend"  # the last compile that finished
        assert "phases" in phase and "other_frac" in phase  # the breakdown reads as before
