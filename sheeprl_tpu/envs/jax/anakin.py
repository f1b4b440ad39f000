"""Anakin fused rollouts: the environment inside the compiled update.

Podracer's Anakin architecture (arXiv:2104.06272) co-locates env stepping
and learning on the chip: a ``lax.scan`` over the batched pure-env step +
policy inference produces the whole rollout as device arrays, which the
algo's existing train phase consumes in the SAME ``fabric.compile``
executable.  Per update there is ONE dispatch and ZERO host↔device data
motion — no Python env workers, no observation shipping, no rollout
staging.  This is the structural answer to classic-control PPO/SAC running
slower on-chip than on host, the chip idling while ``AsyncVectorEnv``
stepped CPU gym processes.

The pieces:

* :func:`make_rollout_fn` — builds the jit-traceable rollout half:
  ``rollout(params, actor, key) -> (actor', rollout, last_obs, stats)``.
  ``actor`` is the persistent device-resident carry (batched ``EnvState``
  + episode accounting + the update counter), donated into each fused
  dispatch so env state lives in HBM across the whole run.
* :func:`init_actor_state` — resets the vector env and stages the carry
  onto the mesh: env-state leaves shard over the ``data`` axis along the
  env dimension (the ``fabric.shard_batch`` layout the train phase's
  minibatch gathers expect), exactly like the PR 9 replay ring.
* :func:`traced_polynomial_decay` — the in-trace twin of
  ``utils.polynomial_decay`` so annealed coefficients (clip/entropy/lr)
  are computed ON DEVICE from the donated update counter: a steady state
  under ``jax.transfer_guard_host_to_device("disallow")`` performs zero
  H2D transfers, explicit or implicit.

Rollout semantics match the host loops: SAME_STEP auto-reset (via
:class:`~sheeprl_tpu.envs.jax.core.VectorJaxEnv`), truncation bootstrap
``r += γ·V(final_obs)`` on truncated rows with the current params, dones =
terminated | truncated.  Vector observations are stored as the policy read
them (float32); a ``uint8`` pixel leaf is stored as the env gave it, its
feature flattened to one lane-dense axis (``u8[T, B, F_pad]``, the replay
ring's rule: ``data/device_replay.stored_feature``), and the train phases
turn what they gather of it back into ``float32 / 255`` frames with
:func:`read_obs_fn`.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.data.device_replay import from_stored, stored_feature
from sheeprl_tpu.envs.jax.core import VectorJaxEnv
from sheeprl_tpu.telemetry.recorder import RECORDER


def traced_polynomial_decay(
    step: jax.Array, *, initial: float, final: float = 0.0, max_decay_steps: int = 100, power: float = 1.0
) -> jax.Array:
    """In-trace twin of ``utils.utils.polynomial_decay`` over a device step
    counter (clamped past ``max_decay_steps``, like the host version)."""
    frac = jnp.clip(1.0 - step.astype(jnp.float32) / float(max_decay_steps), 0.0, 1.0) ** power
    return jnp.float32((initial - final)) * frac + jnp.float32(final)


def _pixels_to_float(x: jax.Array) -> jax.Array:
    """THE pixel normalization: :func:`prep_obs_fn` applies it before the
    policy, :func:`read_obs_fn` after the gather, to the same bytes."""
    return x.astype(jnp.float32) / 255.0


def prep_obs_fn(cnn_keys: Sequence[str], mlp_keys: Sequence[str]) -> Callable:
    """Device-side observation normalization: the traced twin of
    ``ppo.utils.obs_to_np`` (uint8 images → float32/255, vectors →
    float32).  Jax envs don't frame-stack, so no merge branch."""

    def prep(obs: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        out = {}
        for k in cnn_keys:
            out[k] = _pixels_to_float(obs[k])
        for k in mlp_keys:
            out[k] = obs[k].astype(jnp.float32)
        return out

    return prep


def _stored_pixel_feats(cnn_keys: Sequence[str], obs_space: gym.spaces.Dict) -> Dict[str, Tuple[int, ...]]:
    """The leaves the fused rollout stores as the env's bytes: ``uint8``
    leaves of ``cnn_keys`` whose feature has more than one axis, each with
    that feature's shape."""
    return {
        k: tuple(obs_space[k].shape)
        for k in cnn_keys
        if obs_space[k].dtype == np.uint8 and len(obs_space[k].shape) > 1
    }


def read_obs_fn(cnn_keys: Sequence[str], obs_space: gym.spaces.Dict) -> Callable:
    """What turns a rollout's observation leaves into what ``agent.apply``
    takes, on any leading axes: a ``uint8`` leaf of a pixel key is the fused
    rollout's stored form (``(..., F_pad)``: sliced to the feature, reshaped,
    ``float32 / 255``, the same float ``prep_obs_fn`` makes of the same
    byte); every other leaf (vectors; a rollout staged from the host by
    ``obs_to_np``, float32 and normalized already) passes through as the
    same array.  Call it on what was gathered, never on the pool before the
    gather."""
    feats = _stored_pixel_feats(cnn_keys, obs_space)

    def read(obs: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        return {
            k: _pixels_to_float(from_stored(v, feats[k])) if k in feats and v.dtype == jnp.uint8 else v
            for k, v in obs.items()
        }

    return read


def env_actions_fn(action_space: gym.Space) -> Callable:
    """Traced twin of ``ppo.utils.actions_for_env``: stored float actions →
    what the env step consumes."""
    if isinstance(action_space, gym.spaces.Discrete):
        return lambda a: a[..., 0].astype(jnp.int32)
    if isinstance(action_space, gym.spaces.MultiDiscrete):
        return lambda a: a.astype(jnp.int32)
    low = np.asarray(action_space.low, np.float32)
    high = np.asarray(action_space.high, np.float32)
    return lambda a: jnp.clip(a.astype(jnp.float32), low, high)


def init_actor_state(
    fabric: Any,
    venv: VectorJaxEnv,
    key: jax.Array,
    start_update: int,
    sharded: bool,
    extra: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Reset the batched env and stage the persistent actor carry onto the
    mesh: env-dimension leaves shard over ``data`` via the sharding
    engine's env-state spec (``parallel/sharding.env_state_sharding`` —
    the replay-ring placement, one axis earlier) when the env count
    divides the data degree, else replicate.  ``extra`` adds further
    env-leading-axis carry leaves under the same placement law (the
    recurrent loop's LSTM state / prev-action encoding / episode-start
    mask)."""
    from sheeprl_tpu.parallel.sharding import env_state_sharding

    env_state, _ = venv.reset(key)
    actor = {
        "env": env_state,
        "ep_ret": jnp.zeros((venv.num_envs,), jnp.float32),
        "ep_len": jnp.zeros((venv.num_envs,), jnp.int32),
        **(extra or {}),
    }
    placement = (
        env_state_sharding(fabric.mesh, venv.num_envs, fabric.data_axis)
        if sharded
        else fabric.replicated
    )
    actor = jax.device_put(actor, placement)
    actor["update"] = fabric.replicate(jnp.asarray(start_update, jnp.int32))
    return actor


def make_rollout_fn(
    venv: VectorJaxEnv,
    agent_apply: Callable,
    sample_fn: Callable,
    *,
    cnn_keys: Sequence[str],
    mlp_keys: Sequence[str],
    action_space: gym.Space,
    gamma: float,
    rollout_steps: int,
    store_logprobs: bool = True,
) -> Callable:
    """Build ``rollout(p, actor, key) -> (actor', rollout, last_obs, stats)``.

    ``rollout`` leaves are ``(T, B, *feat)`` as the on-policy train phases
    take them (vector obs float32, actions in storage float layout, rewards
    truncation-bootstrapped, dones float), but for ``uint8`` pixel leaves:
    those are ``u8[T, B, F_pad]``, the env's bytes with the feature flattened
    and zero-padded to whole lanes, and the train phases read what they
    gather of them through :func:`read_obs_fn`.  A float frame with a last
    axis of 3 costs four times the bytes and, on a TPU, a relayout of the
    whole pool at the scan's stacking and again at the minibatch gather.
    Each such leaf records one ``rollout.store`` event here.  ``stats``
    carries per-step ``(T, B)`` episode-completion arrays — small, pulled
    D2H by the loop for logging (legal under the H2D-scoped guard).
    """
    prep = prep_obs_fn(cnn_keys, mlp_keys)
    to_env = env_actions_fn(action_space)
    obs_keys = tuple(cnn_keys) + tuple(mlp_keys)
    feats = _stored_pixel_feats(cnn_keys, venv.single_observation_space)
    stored = {k: stored_feature(feat)[0] for k, feat in feats.items()}
    for k, f_pad in stored.items():
        RECORDER.record(
            "rollout.store",
            key=k,
            feature=feats[k],
            stored=(rollout_steps, venv.num_envs, f_pad),
            dtype="uint8",
            bytes=rollout_steps * venv.num_envs * f_pad,
        )

    def store(obs: Dict[str, jax.Array], pobs: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """One step's observation leaves as the scan stacks them: the env's
        bytes for a stored pixel leaf (flattened here, on the uint8 frame,
        where it is a quarter of the float one), the policy's input else."""
        out = {k: pobs[k] for k in obs_keys}
        for k, f_pad in stored.items():
            flat = obs[k].reshape(obs[k].shape[0], -1)
            pad = f_pad - flat.shape[-1]
            out[k] = jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat
        return out

    def rollout(p: Any, actor: Dict[str, Any], key: jax.Array):
        def body(carry, k_step):
            env_state, ep_ret, ep_len = carry
            # named scopes (docs/telemetry.md): where a device trace puts
            # the rollout's time — the render, the policy, the env
            with jax.named_scope("rollout.observe"):
                obs = venv.observe(env_state)
                pobs = prep(obs)
                step_obs = store(obs, pobs)
            with jax.named_scope("rollout.policy"):
                out, value = agent_apply(p, pobs)
                actions, logprob, _ = sample_fn(out, k_step)
            with jax.named_scope("rollout.env_step"):
                env_state, _, reward, term, trunc, final_obs = venv.step(env_state, to_env(actions))
            # truncation bootstrap with the CURRENT params (the host loops'
            # `rewards[truncated] += gamma * V(final_obs)` — here final_obs
            # is always available, no padded re-dispatch needed)
            with jax.named_scope("rollout.policy"):
                _, v_final = agent_apply(p, prep(final_obs))
            trunc_f = trunc.astype(jnp.float32)
            boot_reward = reward + gamma * v_final[..., 0] * trunc_f
            done = jnp.logical_or(term, trunc)
            done_f = done.astype(jnp.float32)
            ep_ret = ep_ret + reward
            ep_len = ep_len + 1
            step_out = {
                **step_obs,
                "actions": actions,
                "logprobs": logprob,
                "rewards": boot_reward,
                "dones": done_f,
                "ep_done": done,
                "ep_ret": ep_ret,
                "ep_len": ep_len,
            }
            ep_ret = ep_ret * (1.0 - done_f)
            ep_len = ep_len * (1 - done.astype(jnp.int32))
            return (env_state, ep_ret, ep_len), step_out

        keys = jax.random.split(key, rollout_steps)
        (env_state, ep_ret, ep_len), traj = jax.lax.scan(
            body, (actor["env"], actor["ep_ret"], actor["ep_len"]), keys
        )
        stats = {k: traj.pop(k) for k in ("ep_done", "ep_ret", "ep_len")}
        if not store_logprobs:
            traj.pop("logprobs")
        with jax.named_scope("rollout.observe"):
            last_obs = prep(venv.observe(env_state))
        new_actor = {
            "env": env_state,
            "ep_ret": ep_ret,
            "ep_len": ep_len,
            "update": actor["update"] + 1,
        }
        return new_actor, traj, last_obs, stats

    return rollout


def make_recurrent_rollout_fn(
    venv: VectorJaxEnv,
    step_apply: Callable,
    sample_fn: Callable,
    encode_prev_actions: Callable,
    *,
    mlp_keys: Sequence[str],
    action_space: gym.Space,
    gamma: float,
    rollout_steps: int,
    store_values: bool = False,
) -> Callable:
    """The recurrent twin of :func:`make_rollout_fn` for ``ppo_recurrent``
    (ROADMAP item 5's remaining half): the policy's per-step method runs
    INSIDE the fused ``lax.scan`` rollout, with the recurrent state,
    previous-action encoding and episode-start mask all living in the
    donated device-resident actor carry.  The recurrent state is any
    pytree with the env axis leading: an LSTM's ``(c, h)``, a decoder's
    caches and positions.

    ``step_apply(p, carry, obs, prev_actions, is_first) -> (carry',
    (actor_out, value))`` is the agent's single-step apply (a third member,
    a dict of per-step arrays, joins the rollout under its keys);
    ``encode_prev_actions(actions)`` is the next-step action encoding
    (one-hot per discrete branch).  Returns ``rollout(p, actor, key) ->
    (actor', rollout, init_carry, last_values, stats)`` where ``rollout``
    carries the extra ``prev_actions``/``is_first`` sequences the
    recurrent train phase consumes, ``init_carry`` is the recurrent state
    at the segment start and ``last_values`` the bootstrap values after
    the last step — everything the existing ``ppo_recurrent`` train phase
    takes, computed without a single host↔device transfer.

    An env with a ``loss_mask(state)`` gives the rollout a ``mask`` key
    beside ``is_first`` (1 where the step's action counts in the losses);
    ``store_values`` keeps the steps' values under ``values``.

    Truncation bootstrap uses the POST-step recurrent state on the true
    final observation (the host loop's padded re-dispatch, in-trace); an
    env that ``never_truncates`` is spared that second policy step.
    """
    prep = prep_obs_fn((), mlp_keys)
    to_env = env_actions_fn(action_space)
    num_envs = venv.num_envs
    never_truncates = bool(getattr(venv.env, "never_truncates", False))
    loss_mask = jax.vmap(venv.env.loss_mask) if hasattr(venv.env, "loss_mask") else None

    def rollout(p: Any, actor: Dict[str, Any], key: jax.Array):
        init_carry = actor["carry"]

        def body(carry, k_step):
            env_state, rc, prev_actions, is_first, ep_ret, ep_len = carry
            pobs = prep(venv.observe(env_state))
            with jax.named_scope("rollout.policy"):
                rc2, (actor_out, value, *told) = step_apply(p, rc, pobs, prev_actions, is_first)
                actions, logprob = sample_fn(actor_out, k_step)
            extra = dict(*told)
            if loss_mask is not None:
                extra["mask"] = loss_mask(env_state)
            if store_values:
                extra["values"] = value[..., 0]
            with jax.named_scope("rollout.env_step"):
                env_state, _, reward, term, trunc, final_obs = venv.step(env_state, to_env(actions))
            prev_a_next = encode_prev_actions(actions)
            if never_truncates:
                boot_reward = reward
            else:
                # truncation bootstrap with the post-step recurrent state
                with jax.named_scope("rollout.policy"):
                    _, (_, v_final, *_) = step_apply(
                        p, rc2, prep(final_obs), prev_a_next,
                        jnp.zeros((num_envs, 1), jnp.float32),
                    )
                trunc_f = trunc.astype(jnp.float32)
                boot_reward = reward + gamma * v_final[..., 0] * trunc_f
            done = jnp.logical_or(term, trunc)
            done_f = done.astype(jnp.float32)
            ep_ret = ep_ret + reward
            ep_len = ep_len + 1
            step_out = {
                **pobs,
                "actions": actions,
                "logprobs": logprob,
                "rewards": boot_reward,
                "dones": done_f,
                "is_first": is_first,
                "prev_actions": prev_actions,
                **extra,
                "ep_done": done,
                "ep_ret": ep_ret,
                "ep_len": ep_len,
            }
            ep_ret = ep_ret * (1.0 - done_f)
            ep_len = ep_len * (1 - done.astype(jnp.int32))
            # episode boundary resets the next step's recurrent inputs
            prev_a_next = prev_a_next * (1.0 - done_f[..., None])
            is_first_next = done_f[..., None]
            return (env_state, rc2, prev_a_next, is_first_next, ep_ret, ep_len), step_out

        keys = jax.random.split(key, rollout_steps)
        (env_state, carry2, prev_actions, is_first, ep_ret, ep_len), traj = jax.lax.scan(
            body,
            (
                actor["env"], actor["carry"], actor["prev_actions"],
                actor["is_first"], actor["ep_ret"], actor["ep_len"],
            ),
            keys,
        )
        stats = {k: traj.pop(k) for k in ("ep_done", "ep_ret", "ep_len")}
        # bootstrap values for the post-rollout state, with the live carry
        with jax.named_scope("rollout.policy"):
            _, (_, last_v, *_) = step_apply(
                p, carry2, prep(venv.observe(env_state)), prev_actions, is_first
            )
        new_actor = {
            "env": env_state,
            "carry": carry2,
            "prev_actions": prev_actions,
            "is_first": is_first,
            "ep_ret": ep_ret,
            "ep_len": ep_len,
            "update": actor["update"] + 1,
        }
        return new_actor, traj, init_carry, last_v[..., 0], stats

    return rollout


def episode_stats_from_device(stats: Dict[str, jax.Array]) -> Tuple[np.ndarray, np.ndarray]:
    """Pull the per-step completion arrays D2H and flatten to the finished
    episodes' ``(returns, lengths)`` — the fused path's counterpart of
    ``utils.env.episode_stats``."""
    done = np.asarray(stats["ep_done"]).reshape(-1)
    rets = np.asarray(stats["ep_ret"]).reshape(-1)[done]
    lens = np.asarray(stats["ep_len"]).reshape(-1)[done]
    return rets, lens
