"""Anakin fused rollouts (envs/jax/anakin.py + ppo/a2c integration).

The contract under test (ISSUE 11 acceptance):

* 50 fused rollout iterations reuse ONE compiled executable — env state,
  episode accounting and the update counter are device data, not
  signature.
* PPO/A2C on ``env=jax_cartpole`` train multi-window runs end-to-end
  through the CLI with the transfer guard armed over every post-warmup
  window and ``algo.max_recompiles=1`` — a fused path that ships
  anything H2D in steady state, or churns executable signatures, dies
  here red.
* ``algo.anakin`` mode resolution (auto / forced / disabled) behaves.
"""

from typing import NamedTuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sheeprl_tpu.cli import run
from sheeprl_tpu.envs.jax.cartpole import JaxCartPole
from sheeprl_tpu.envs.jax.core import VectorJaxEnv
from sheeprl_tpu.envs.jax.registry import anakin_enabled
from sheeprl_tpu.parallel.fabric import Fabric


def _anakin_args(tmp_path, exp, extra=()):
    return [
        f"exp={exp}",
        "env=jax_cartpole",
        "env.num_envs=2",
        "env.capture_video=False",
        "fabric.devices=1",
        "fabric.accelerator=cpu",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=8",
        "algo.total_steps=48",  # 3 fused windows: guard arms from window 2
        "algo.mlp_keys.encoder=[state]",
        "algo.max_recompiles=1",
        "buffer.transfer_guard=True",
        "metric.log_level=1",
        "metric.log_every=1",
        "checkpoint.every=0",
        "checkpoint.save_last=False",
        "buffer.memmap=False",
        f"log_dir={tmp_path}/logs",
        "print_config=False",
        "algo.run_test=False",
        *extra,
    ]


class TestFusedExecutableReuse:
    def test_cache_size_one_across_50_rollout_iterations(self):
        from sheeprl_tpu.algos.ppo.agent import sample_actions
        from sheeprl_tpu.envs.jax.anakin import init_actor_state, make_rollout_fn

        fabric = Fabric(devices=1, accelerator="cpu")
        venv = VectorJaxEnv(JaxCartPole(), 4)

        def apply(p, obs):
            h = obs["state"] @ p["w"]
            return h[:, :2], h[:, 2:3]

        def sample(out, k):
            return sample_actions(out, (2,), False, k)

        rollout_fn = make_rollout_fn(
            venv, apply, sample,
            cnn_keys=(), mlp_keys=("state",),
            action_space=venv.single_action_space,
            gamma=0.99, rollout_steps=5,
        )

        def fused(p, actor, k):
            k_roll, k_next = jax.random.split(k)
            actor, rollout, last_obs, stats = rollout_fn(p, actor, k_roll)
            # a stand-in "train": fold the rollout into a param delta so
            # params depend on the whole fused trajectory
            delta = jnp.mean(rollout["state"]) + jnp.mean(rollout["rewards"])
            return {"w": p["w"] + 0.0 * delta}, actor, k_next, stats

        fused = fabric.compile(fused, name="test.anakin_fused", donate_argnums=(1,))
        params = {"w": jnp.zeros((4, 3), jnp.float32)}
        actor = init_actor_state(fabric, venv, jax.random.PRNGKey(0), 0, sharded=True)
        key = jax.random.PRNGKey(1)
        for i in range(50):
            params, actor, key, stats = fused(params, actor, key)
        assert fused.cache_size() == 1
        assert int(np.asarray(actor["update"])) == 50
        # episodes completed and were accounted during the 250 fused steps
        assert np.asarray(stats["ep_done"]).dtype == np.bool_

    def test_rollout_layout_matches_train_contract(self):
        from sheeprl_tpu.algos.ppo.agent import sample_actions
        from sheeprl_tpu.envs.jax.anakin import init_actor_state, make_rollout_fn

        fabric = Fabric(devices=1, accelerator="cpu")
        venv = VectorJaxEnv(JaxCartPole(), 3)

        def apply(p, obs):
            h = obs["state"] @ p["w"]
            return h[:, :2], h[:, 2:3]

        rollout_fn = make_rollout_fn(
            venv, apply, lambda out, k: sample_actions(out, (2,), False, k),
            cnn_keys=(), mlp_keys=("state",),
            action_space=venv.single_action_space,
            gamma=0.99, rollout_steps=7,
        )
        actor = init_actor_state(fabric, venv, jax.random.PRNGKey(0), 0, sharded=True)
        params = {"w": jnp.zeros((4, 3), jnp.float32)}
        actor2, rollout, last_obs, stats = jax.jit(rollout_fn)(
            params, actor, jax.random.PRNGKey(2)
        )
        # (T, B, *) layout, float obs, storage-format actions — exactly what
        # the on-policy train phases consume from the host staging path
        assert rollout["state"].shape == (7, 3, 4) and rollout["state"].dtype == jnp.float32
        assert rollout["actions"].shape == (7, 3, 1)
        assert rollout["logprobs"].shape == (7, 3)
        assert rollout["rewards"].shape == (7, 3)
        assert rollout["dones"].shape == (7, 3) and rollout["dones"].dtype == jnp.float32
        assert last_obs["state"].shape == (3, 4)
        assert int(np.asarray(actor2["update"])) == 1


class _StackedFrames:
    """An 84 x 84 x 4 uint8 box (the Atari shape): the frame is a fixed pattern shifted by the step count,
    so every step's frame differs and 84*84*4 = 28224 is no lane multiple."""

    class State(NamedTuple):
        key: jax.Array
        t: jax.Array

    max_episode_steps = 5

    def __init__(self):
        from gymnasium import spaces

        self.observation_space = spaces.Dict({"rgb": spaces.Box(0, 255, (84, 84, 4), np.uint8)})
        self.action_space = spaces.Discrete(3)

    def reset(self, key):
        state = self.State(key=key, t=jax.random.randint(key, (), 0, 200))
        return state, self.observe(state)

    def step(self, state, action):
        state = state._replace(t=state.t + 1 + action)
        return state, self.observe(state), jnp.float32(1.0), jnp.bool_(False), state.t % 7 == 0

    def observe(self, state):
        pattern = jnp.arange(84 * 84 * 4, dtype=jnp.int32).reshape(84, 84, 4) * 31
        return {"rgb": ((pattern + state.t) % 256).astype(jnp.uint8)}


def _store_case(name):
    from sheeprl_tpu.envs.jax.forage import JaxForage
    from sheeprl_tpu.envs.jax.multiroom import JaxMultiRoom

    return {
        "jax_multiroom": (JaxMultiRoom, "rgb", (64, 64, 3)),
        "jax_forage": (JaxForage, "rgb", (64, 64, 3)),
        "box_84x84x4": (_StackedFrames, "rgb", (84, 84, 4)),
        "jax_cartpole": (JaxCartPole, "state", (4,)),
    }[name]


@pytest.mark.parametrize("name", ["jax_multiroom", "jax_forage", "box_84x84x4", "jax_cartpole"])
def test_rollout_stores_pixels_as_the_envs_bytes_and_the_reader_gives_the_policys_frames(name):
    """A pixel leaf leaves ``make_rollout_fn`` as ``uint8 (T, B, F_pad)`` with whole lanes, a vector leaf
    as ``float32 (T, B, F)``; ``read_obs_fn`` on the stored leaf is ``prep(venv.observe(state))`` of every
    step to the bit, and a float32 leaf staged from the host passes through it as the same array."""
    from sheeprl_tpu.envs.jax.anakin import (
        env_actions_fn, init_actor_state, make_rollout_fn, prep_obs_fn, read_obs_fn,
    )
    from sheeprl_tpu.telemetry.recorder import RECORDER

    make_env, key, feat = _store_case(name)
    T, B = 5, 3
    pixels = len(feat) > 1
    cnn_keys, mlp_keys = ((key,), ()) if pixels else ((), (key,))
    fabric = Fabric(devices=1, accelerator="cpu")
    venv = VectorJaxEnv(make_env(), B)
    n_actions = int(venv.single_action_space.n)

    def apply(p, obs):  # a policy that reads its frame, so a wrong frame would show in the actions
        h = obs[key].reshape(B, -1)[:, :4] @ p["w"]
        return h, h[:, :1]

    def sample(out, k):
        actions = jax.random.randint(k, (B, 1), 0, n_actions).astype(jnp.float32)
        return actions, jnp.zeros((B,), jnp.float32), None

    RECORDER.clear()
    rollout_fn = make_rollout_fn(
        venv, apply, sample, cnn_keys=cnn_keys, mlp_keys=mlp_keys,
        action_space=venv.single_action_space, gamma=0.99, rollout_steps=T,
    )
    events = [e for e in RECORDER.snapshot() if e["kind"] == "rollout.store"]
    actor = init_actor_state(fabric, venv, jax.random.PRNGKey(0), 0, sharded=True)
    env_state = actor["env"]
    k_roll = jax.random.PRNGKey(2)
    params = {"w": jnp.ones((4, n_actions), jnp.float32)}
    _, rollout, last_obs, _ = jax.jit(rollout_fn)(params, actor, k_roll)

    # the frames the policy read, step by step, from the same env states
    prep = prep_obs_fn(cnn_keys, mlp_keys)
    to_env = env_actions_fn(venv.single_action_space)
    seen = []
    for k_step in jax.random.split(k_roll, T):
        seen.append(np.asarray(prep(venv.observe(env_state))[key]))
        env_state = venv.step(env_state, to_env(sample(None, k_step)[0]))[0]
    seen = np.stack(seen)
    assert len({frame.tobytes() for frame in seen}) > 1  # the env moved

    read = read_obs_fn(cnn_keys, venv.single_observation_space)
    stored = rollout[key]
    if pixels:
        f_pad = -(-int(np.prod(feat)) // 128) * 128
        assert stored.dtype == jnp.uint8 and stored.shape == (T, B, f_pad) and f_pad % 128 == 0
        assert not np.asarray(stored[..., int(np.prod(feat)):]).any()  # the padding is zeros
        (event,) = events
        assert event["key"] == key and tuple(event["feature"]) == feat and event["dtype"] == "uint8"
        assert tuple(event["stored"]) == (T, B, f_pad) and event["bytes"] == T * B * f_pad == stored.nbytes
    else:
        assert stored.dtype == jnp.float32 and stored.shape == (T, B) + feat
        assert not events
    back = read({key: stored})[key]
    assert back.dtype == jnp.float32 and back.shape == (T, B) + feat
    # bytes are exact; cartpole's float dynamics round differently stepped here than fused in the scan
    same = np.testing.assert_array_equal if pixels else (lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5))
    same(np.asarray(back), seen)
    np.testing.assert_array_equal(np.asarray(back[0]), seen[0])
    # on any leading axes: a gathered minibatch of the flat pool
    flat = stored.reshape((T * B,) + stored.shape[2:])
    same(np.asarray(read({key: flat[jnp.array([7, 2])]})[key]), seen.reshape((T * B,) + feat)[[7, 2]])
    # what the host loops stage (float32, normalized already) is handed on as it is
    staged = jnp.asarray(seen)
    assert read({key: staged})[key] is staged
    # the bootstrap frame is the live one, as the policy takes it
    assert last_obs[key].dtype == jnp.float32 and last_obs[key].shape == (B,) + feat


def test_decoupled_train_phase_reads_a_stored_rollout_as_the_float_one():
    """The PPO program behind Sebulba's fused actors (``ppo_decoupled._build_train_fns``): a rollout
    whose pixel leaf is the fused store's ``uint8 (T, B, F_pad)`` trains to the bit as the same rollout
    staged in float32, the form the host workers send."""
    from gymnasium import spaces

    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo_decoupled import _build_train_fns
    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.envs.jax.anakin import read_obs_fn
    from sheeprl_tpu.utils.optim import build_optimizer

    T, B = 4, 2
    cfg = compose([
        "exp=ppo_decoupled", "env=jax_multiroom", "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[]",
        "algo.update_epochs=1", "fabric.accelerator=cpu",
    ])
    fabric = Fabric(devices=1, accelerator="cpu")
    obs_space = spaces.Dict({"rgb": spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    agent, params = build_agent(fabric, (5,), False, cfg, obs_space)
    optimizer = build_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm)
    train_phase = _build_train_fns(agent, optimizer, cfg, ("rgb",), (5,), False, "auto", obs_space)[3]

    rng = np.random.default_rng(0)
    stored = jnp.asarray(rng.integers(0, 256, (T, B, 64 * 64 * 3), dtype=np.uint8))
    rollout = {
        "rgb": stored,
        "actions": jnp.asarray(rng.integers(0, 5, (T, B, 1)).astype(np.float32)),
        "logprobs": jnp.full((T, B), -1.6, jnp.float32),
        "rewards": jnp.asarray(rng.normal(size=(T, B)).astype(np.float32)),
        "dones": jnp.zeros((T, B), jnp.float32),
    }
    read = read_obs_fn(("rgb",), obs_space)
    # under jit, as the fused rollout normalized them: XLA divides by a constant its own way
    staged = dict(rollout, **jax.jit(read)({"rgb": stored}))
    assert staged["rgb"].shape == (T, B, 64, 64, 3) and staged["rgb"].dtype == jnp.float32
    last_obs = {"rgb": staged["rgb"][-1]}

    def run_phase(rollout):
        return jax.jit(train_phase, static_argnames=("batch_size", "num_minibatches"))(
            params, optimizer.init(params), rollout, last_obs, jax.random.PRNGKey(3),
            jnp.float32(0.2), jnp.float32(0.01), batch_size=4, num_minibatches=2,
        )

    for a, b in zip(jax.tree.leaves(run_phase(rollout)), jax.tree.leaves(run_phase(staged))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestAnakinEndToEnd:
    def test_ppo_multiwindow_guarded(self, tmp_path):
        run(_anakin_args(tmp_path, "ppo", extra=["algo.update_epochs=1"]))

    def test_a2c_multiwindow_guarded_annealed(self, tmp_path):
        run(_anakin_args(tmp_path, "a2c", extra=["algo.anneal_lr=True"]))

    def test_ppo_recurrent_multiwindow_guarded(self, tmp_path):
        # ISSUE 12 satellite (ROADMAP item 5 remaining): the nn.scan LSTM
        # policy fused into the rollout scan — recurrent state, prev-action
        # encoding and episode-start mask all live in the donated carry, so
        # the armed guard + compile budget prove zero steady-state H2D
        run(
            _anakin_args(
                tmp_path, "ppo_recurrent",
                extra=[
                    "env.mask_velocities=False",
                    "algo.update_epochs=1",
                    "algo.per_rank_sequence_length=4",
                    "algo.anneal_lr=True",
                    "algo.anneal_ent_coef=True",
                ],
            )
        )

    def test_ppo_recurrent_adapter_fallback_when_disabled(self, tmp_path):
        run(
            _anakin_args(
                tmp_path, "ppo_recurrent",
                extra=[
                    "env.mask_velocities=False",
                    "algo.update_epochs=1",
                    "algo.per_rank_sequence_length=4",
                    "algo.anakin=False",
                    "dry_run=True",
                ],
            )
        )

    def test_ppo_adapter_fallback_when_disabled(self, tmp_path):
        # algo.anakin=False: same jax env through JaxToGymAdapter +
        # vector-env machinery (guard still green: staging is explicit)
        run(
            _anakin_args(tmp_path, "ppo", extra=["algo.anakin=False", "dry_run=True"])
        )


class TestModeResolution:
    def _cfg(self, overrides=()):
        from sheeprl_tpu.config.compose import compose

        return compose(["exp=ppo", "algo.mlp_keys.encoder=[state]", *overrides])

    def test_auto_on_jax_env_single_process(self):
        fabric = Fabric(devices=1, accelerator="cpu")
        assert anakin_enabled(self._cfg(["env=jax_cartpole"]), fabric)

    def test_auto_off_on_gym_env(self):
        fabric = Fabric(devices=1, accelerator="cpu")
        assert not anakin_enabled(self._cfg(["env=gym"]), fabric)

    def test_forced_on_non_jax_env_raises(self):
        fabric = Fabric(devices=1, accelerator="cpu")
        with pytest.raises(ValueError, match="anakin"):
            anakin_enabled(self._cfg(["env=gym", "algo.anakin=True"]), fabric)

    def test_disabled_wins(self):
        fabric = Fabric(devices=1, accelerator="cpu")
        assert not anakin_enabled(
            self._cfg(["env=jax_cartpole", "algo.anakin=False"]), fabric
        )
