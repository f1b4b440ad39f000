"""The spans as the loops open them, end to end on the CPU: a DreamerV3 dry run
on the device ring and an Anakin PPO run each yield one ``iter`` per loop
iteration whose descendants are the program's layers; and the programs they
compile carry the named device scopes in their debug text."""

import re

import jax
import numpy as np
import pytest

from sheeprl_tpu.cli import run
from sheeprl_tpu.parallel.compile import AOTFunction
from sheeprl_tpu.telemetry import SPANS
from sheeprl_tpu.telemetry.spans import ITER

COMMON = [
    "env.capture_video=False", "fabric.devices=1", "fabric.accelerator=cpu",
    "buffer.memmap=False", "metric.log_level=1", "metric.log_every=1",
    "algo.run_test=False", "print_config=False",
]


def _run_and_collect(args):
    """Run the CLI once; return (closed spans, {program name: lowered debug text})."""
    lowered = {}
    real_lookup = AOTFunction._lookup

    def lookup(self, sig, args_, kwargs_):
        if self.name not in lowered:
            lowered[self.name] = self._jitted.lower(*args_, **kwargs_).as_text(debug_info=True)
        return real_lookup(self, sig, args_, kwargs_)

    SPANS.reset()
    AOTFunction._lookup = lookup
    try:
        run(args)
    finally:
        AOTFunction._lookup = real_lookup
    return SPANS.records(), lowered


@pytest.fixture(scope="module")
def dv3(tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("dv3")
    return _run_and_collect([
        "exp=dreamer_v3", "env=dummy", "env.id=discrete_dummy", "dry_run=True",
        "env.num_envs=2", "env.sync_env=True", "buffer.size=512",
        "buffer.device=True", "buffer.transfer_guard=True",
        "checkpoint.every=0", "checkpoint.save_last=True",
        "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[state]",
        "algo.horizon=4", "algo.dense_units=16", "algo.mlp_layers=1",
        "algo.world_model.encoder.cnn_channels_multiplier=4",
        "algo.world_model.recurrent_model.recurrent_state_size=16",
        "algo.world_model.transition_model.hidden_size=16",
        "algo.world_model.representation_model.hidden_size=16",
        "algo.world_model.discrete_size=4", "algo.world_model.stochastic_size=4",
        "algo.per_rank_batch_size=2", "algo.per_rank_sequence_length=8",
        f"log_dir={log_dir}",
    ] + COMMON)


@pytest.fixture(scope="module")
def anakin(tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("anakin")
    return _run_and_collect([
        "exp=ppo", "env=jax_cartpole", "env.num_envs=2",
        "algo.rollout_steps=8", "algo.per_rank_batch_size=8", "algo.update_epochs=1",
        "algo.total_steps=48",  # 3 fused dispatches
        "algo.mlp_keys.encoder=[state]", "algo.max_recompiles=1",
        "checkpoint.every=32", "checkpoint.save_last=False",
        f"log_dir={log_dir}",
    ] + COMMON)


def carries(text, scope):
    """A lowered program's debug text names the scope: as a path component, first (``"wm.optim/mul"``
    inside a sub-function), in the middle, or under ``jvp(...)``/``transpose(...)``."""
    return re.search(r'["/(]' + re.escape(scope) + r'[/)"]', text) is not None


def descendants(records):
    """{iteration: names of the spans under its ``iter`` on the loop's thread}, by parent links."""
    by_id = {r.id: r for r in records}
    out = {}
    for r in records:
        if r.name == ITER:
            out.setdefault(r.iteration, [])
            continue
        node = r
        while node.parent is not None and node.parent in by_id:
            node = by_id[node.parent]
        if node.name == ITER:
            out.setdefault(node.iteration, []).append(r.name)
    return out


class TestDreamerV3:
    def test_one_iter_span_per_loop_iteration(self, dv3):
        records, _ = dv3
        iters = [r for r in records if r.name == ITER]
        # dry run: 2 x sequence length + 4 iterations, numbered by the loop's `update`
        assert [r.iteration for r in iters] == list(range(1, 21))
        assert all(a.end <= b.start for a, b in zip(iters, iters[1:]))
        assert all(r.parent is None and r.thread == "MainThread" for r in iters)

    def test_every_iteration_holds_the_env_the_ring_and_the_player(self, dv3):
        under = descendants(dv3[0])
        assert sorted(under) == list(range(1, 21))
        for names in under.values():
            assert {"rollout", "env.step", "replay.write", "exec.dreamer_v3.player_step"} <= set(names)
            assert names.count("env.step") == 1  # one span a loop step, over all envs

    def test_the_training_iteration_holds_dispatch_sync_and_program(self, dv3):
        records, _ = dv3
        under = descendants(records)
        last = under[20]
        assert "update.dispatch" in last and last.count("player.sync") == 2
        assert "exec.dreamer_v3.train_phase_device" in last
        assert {"log.flush", "ckpt.save"} <= set(last)
        assert "update.dispatch" not in under[1]
        by_id = {r.id: r for r in records}
        dispatch = next(r for r in records if r.name == "update.dispatch")
        assert by_id[dispatch.parent].name == ITER  # top-level under the frame
        for r in records:
            if r.name in ("player.sync", "exec.dreamer_v3.train_phase_device"):
                assert r.parent == dispatch.id
            if r.name in ("env.step", "replay.write", "exec.dreamer_v3.player_step"):
                assert by_id[r.parent].name == "rollout"

    def test_the_one_count_is_the_pull_and_phase_keys_are_the_phases(self, dv3):
        records, _ = dv3
        # a count is kept where a metric reads it: the bytes of the weight pull (none in a run that trains once),
        # the process's age on the set-up root and whether a backend compile was a cache read
        counted = {"player.sync": {"bytes"}, "setup": {"pre_run_ms"}, "compile.backend": {"cache_hit"}}
        assert all(set(r.counts or ()) <= counted.get(r.name, set()) for r in records)
        assert all((r.counts or {}).get("bytes", 0) == 0 for r in records if r.name == "player.sync")
        # the boundaries (iter, exec.*, env.step, player.sync, log.flush, ckpt.save) are no part of Phase/*
        assert set(SPANS.breakdown()["phases"]) <= {"rollout", "update.dispatch", "replay.write", "ckpt.snapshot"}

    def test_the_writer_thread_snapshot_hangs_under_the_save(self, dv3):
        records, _ = dv3
        save = next(r for r in records if r.name == "ckpt.save")
        snap = next(r for r in records if r.name == "ckpt.snapshot")
        assert snap.parent == save.id and snap.iteration == save.iteration == 20
        assert snap.thread != save.thread

    def test_liveness_counts_the_one_dispatch(self, dv3):
        # the fixture ran in this process: the log above is what /healthz counted
        assert sum(1 for r in dv3[0] if r.name == "update.dispatch") == 1

    @pytest.mark.parametrize("scope", [
        "replay.sample_index", "replay.gather", "wm.encoder", "wm.rssm", "wm.heads", "wm.optim",
        "behavior.imagine", "actor.loss", "actor.optim", "critic.loss", "critic.optim",
    ])
    def test_train_phase_carries_the_scope(self, dv3, scope):
        assert carries(dv3[1]["dreamer_v3.train_phase_device"], scope)

    def test_player_step_carries_its_scope(self, dv3):
        assert carries(dv3[1]["dreamer_v3.player_step"], "player.step")
        assert not carries(dv3[1]["dreamer_v3.player_step"], "wm.encoder")


class TestAnakin:
    def test_one_iter_per_dispatch_with_the_fused_program_under_it(self, anakin):
        records, _ = anakin
        under = descendants(records)
        assert sorted(under) == [1, 2, 3]
        for names in under.values():
            assert names.count("update.dispatch") == 1 and names.count("exec.ppo.anakin_phase") == 1
            assert "log.flush" in names
            assert "env.step" not in names and "rollout" not in names  # the env is inside the program
        by_id = {r.id: r for r in records}
        for r in records:
            if r.name == "exec.ppo.anakin_phase":
                assert by_id[r.parent].name == "update.dispatch"

    def test_the_snapshot_cadence_shows_as_ckpt_save(self, anakin):
        under = descendants(anakin[0])
        # checkpoint.every=32 policy steps, 16 a dispatch: every second one saves
        assert ["ckpt.save" in under[i] for i in (1, 2, 3)] == [False, True, False]

    @pytest.mark.parametrize("scope", [
        "rollout.policy", "rollout.env_step", "rollout.observe", "gae",
        "update.gather", "update.loss", "update.optim",
    ])
    def test_fused_phase_carries_the_scope(self, anakin, scope):
        assert carries(anakin[1]["ppo.anakin_phase"], scope)


class TestRingWrite:
    def test_the_donated_scatter_carries_its_scope_and_keeps_its_name(self):
        from sheeprl_tpu.data.device_replay import DeviceReplay

        ring = DeviceReplay(16, 2)
        ring.add({"rgb": np.zeros((1, 2, 8, 8, 3), np.uint8), "rewards": np.zeros((1, 2, 1), np.float32)})
        scatter, _, _ = ring._ops()
        arr = ring.buffers["rgb"]
        rows = jax.ShapeDtypeStruct((1, 2) + arr.shape[2:], np.uint8)  # a pixel leaf is stored flat
        idx = jax.ShapeDtypeStruct((1, 2), np.int32)
        env = jax.ShapeDtypeStruct((2,), np.int32)
        text = scatter.lower(jax.ShapeDtypeStruct(arr.shape, arr.dtype), rows, idx, env).as_text(debug_info=True)
        assert carries(text, "replay.write")
        # the module's name is part of the compile cache's key: the scope must not rename it
        assert "module @jit__lambda" in text
        (write,) = [r for r in SPANS.records() if r.name == "replay.write"]
        assert write.counts is None and write.parent is None


def test_env_step_span_wraps_what_vectorize_returns():
    import gymnasium as gym

    from sheeprl_tpu.utils.env import vectorize
    from sheeprl_tpu.utils.structured import dotdict

    cfg = dotdict({"env": {"sync_env": True}})
    envs = vectorize(cfg, [lambda: gym.make("CartPole-v1") for _ in range(3)])
    try:
        assert isinstance(envs, gym.vector.SyncVectorEnv)  # the vector env keeps its type
        envs.reset(seed=0)
        envs.step(np.zeros(3, np.int64))
    finally:
        envs.close()
    (step,) = [r for r in SPANS.records() if r.name == "env.step"]
    assert step.counts is None and step.thread == "MainThread"
    assert "env.step" not in SPANS.breakdown()["phases"]  # a boundary: a record and an annotation


@pytest.mark.parametrize("crosses", [False, True], ids=["beside_the_train_state", "to_another_platform"])
def test_player_sync_counts_the_bytes_it_pulls(crosses):
    from types import SimpleNamespace

    import jax.numpy as jnp

    from sheeprl_tpu.parallel.fabric import Fabric, PlayerSync
    from sheeprl_tpu.utils.structured import dotdict

    fab = Fabric(devices=1, accelerator="cpu")
    cfg = dotdict({"algo": {"player": {"deferred_sync": True, "sync_every": 1, "device": "host"}}})
    psync = PlayerSync(fab, cfg, extract=lambda p: p["actor"])
    player = psync.init({"actor": jnp.zeros((3, 2), jnp.float32)})
    if crosses:
        # no second platform here: a player device of another platform, and the copy stood in for
        psync.device = SimpleNamespace(platform="host-of-a-chip")
        fab.copy_to = lambda tree, device, into=None: tree
    SPANS.reset()
    player = psync.after_dispatch({"actor": jnp.ones((3, 2), jnp.float32)}, player_params=player)  # deferred: nothing moves
    player = psync.before_dispatch(player)  # the pending weights land: 6 float32
    psync.before_dispatch(player)  # nothing pending
    syncs = [r for r in SPANS.records() if r.name == "player.sync"]
    assert [(r.counts or {}).get("bytes", 0) for r in syncs] == [0, 24 if crosses else 0, 0]
    assert float(player[0, 0]) == 1.0
    assert "player.sync" not in SPANS.breakdown()["phases"]
