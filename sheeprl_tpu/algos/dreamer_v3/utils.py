"""DreamerV3 support utilities
(reference: sheeprl/algos/dreamer_v3/utils.py:20-235)."""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.utils.utils import merge_framestack

AGGREGATOR_KEYS = {
    "Rewards/rew_avg",
    "Game/ep_len_avg",
    "Loss/world_model_loss",
    "Loss/value_loss",
    "Loss/policy_loss",
    "Loss/observation_loss",
    "Loss/reward_loss",
    "Loss/state_loss",
    "Loss/continue_loss",
    "State/kl",
    "State/post_entropy",
    "State/prior_entropy",
}
MODELS_TO_REGISTER = {"world_model", "actor", "critic", "target_critic", "moments"}


def moments_update(
    moments: Dict[str, jax.Array],
    x: jax.Array,
    decay: float = 0.99,
    max_: float = 1.0,
    plow: float = 0.05,
    phigh: float = 0.95,
) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array]:
    """Return-percentile normalizer (reference: utils.py:40-63 ``Moments``).

    The reference all-gathers across ranks before the quantile; here ``x`` is
    the GLOBAL (mesh-wide) batch inside the jitted step, so the quantile is
    already world-synchronized by GSPMD.
    Returns (new_moments, offset, invscale).
    """
    x = jax.lax.stop_gradient(x.astype(jnp.float32))
    low = jnp.quantile(x, plow)
    high = jnp.quantile(x, phigh)
    new_low = decay * moments["low"] + (1 - decay) * low
    new_high = decay * moments["high"] + (1 - decay) * high
    invscale = jnp.maximum(1.0 / max_, new_high - new_low)
    return {"low": new_low, "high": new_high}, new_low, invscale


def compute_lambda_values(
    rewards: jax.Array, values: jax.Array, continues: jax.Array, lmbda: float = 0.95
) -> jax.Array:
    """TD(λ) over imagined steps (reference: utils.py:66-77).

    Index t of every input corresponds to imagination step t+1; ``continues``
    already folds in γ.  Recursion: out[t] = r[t] + c[t]·((1-λ)·v[t] +
    λ·out[t+1]), bootstrapped with v[last].
    """

    def step(next_ret, xs):
        r, v, c = xs
        ret = r + c * ((1 - lmbda) * v + lmbda * next_ret)
        return ret, ret

    _, rets = jax.lax.scan(step, values[-1], (rewards, values, continues), reverse=True)
    return rets


def prepare_obs(
    obs: Dict[str, np.ndarray], cnn_keys: Sequence[str] = (), mlp_keys: Sequence[str] = ()
) -> Dict[str, jax.Array]:
    """uint8 images → [-0.5, 0.5] floats; vectors → float32 (the symlog is
    inside the encoder).  (reference: utils.py:80-91)."""
    out: Dict[str, jax.Array] = {}
    for k in cnn_keys:
        x = np.asarray(obs[k])
        if x.ndim == 5:  # (B, S, H, W, C) frame stack → channels
            x = merge_framestack(x)
        out[k] = jnp.asarray(x, jnp.float32) / 255.0 - 0.5
    for k in mlp_keys:
        out[k] = jnp.asarray(np.asarray(obs[k], np.float32).reshape(np.asarray(obs[k]).shape[0], -1))
    return out


def test(
    player_step_fn: Any,
    player_state: Any,
    cfg: Any,
    log_dir: str,
    logger: Any = None,
    greedy: bool = True,
) -> float:
    """Greedy evaluation episode with the latent-state player
    (reference: utils.py:94-139)."""
    from sheeprl_tpu.algos.ppo.utils import actions_for_env
    from sheeprl_tpu.utils.env import make_env

    env = make_env(cfg, cfg.seed, 0, run_name=log_dir, prefix="test")()
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs, _ = env.reset(seed=cfg.seed)
    carry = None
    done, cum_reward = False, 0.0
    # everything the episode creates (key, observations, the first carry) is
    # uncommitted: make it on the PLAYER's device.  On the default device (the
    # accelerator, with a host player) the first step would reach player_step
    # with one placement and every later one, whose carry comes back from the
    # player's device, with another — a recompile only a chip shows.
    player_device = next(iter(jax.tree.leaves(player_state)[0].devices()))
    with jax.default_device(player_device):
        key = jax.random.PRNGKey(cfg.seed)
        while not done:
            batched = {k: np.asarray(v)[None] for k, v in obs.items()}
            o = prepare_obs(batched, cnn_keys, mlp_keys)
            key, sk = jax.random.split(key)
            carry, env_action = player_step_fn(player_state, carry, o, sk, greedy)
            obs, reward, terminated, truncated, _ = env.step(
                actions_for_env(np.asarray(env_action), env.action_space)[0]
            )
            done = bool(terminated or truncated)
            cum_reward += float(reward)
    env.close()
    if logger is not None:
        logger.log_metrics({"Test/cumulative_reward": cum_reward}, 0)
    return cum_reward


def normalize_obs_block(data, cnn_keys, obs_keys, offset: float = 0.5):
    """Device-side observation normalization of a uint8-shipped replay block:
    images → float/255 − offset, vectors → float (the jit-side twin of
    :func:`prepare_obs`)."""
    import jax.numpy as jnp

    return {
        kk: (data[kk].astype(jnp.float32) / 255.0 - offset)
        if kk in cnn_keys
        else data[kk].astype(jnp.float32)
        for kk in obs_keys
    }
