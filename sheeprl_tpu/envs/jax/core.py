"""On-device vectorized environments: the pure-function env contract.

The Anakin pattern (Podracer, arXiv:2104.06272) puts the environment INSIDE
the jitted step so one chip steps thousands of env instances with zero host
round-trips — the structural fix for PPO/SAC classic-control running
*slower* on-chip because the chip idled while Python gym workers stepped
envs and shipped observations.

Env authoring contract (docs/jax_envs.md):

* **State is an explicit pytree** — a ``NamedTuple`` whose leaves are JAX
  arrays, carrying EVERYTHING the env needs between steps, including a
  ``key`` field holding the instance's own PRNG stream.  No Python-side
  state; ``step``/``reset`` are pure, jit-traceable functions.
* ``reset(key) -> (state, obs)`` — consumes the key (storing a derived
  carry key in ``state.key`` for later stochasticity/auto-reset reseeds).
* ``step(state, action) -> (state, obs, reward, terminated, truncated)`` —
  single-instance semantics; gymnasium flag split (``terminated`` = MDP
  terminal state, ``truncated`` = time/step limit).  Truncation is the
  env's own job here (there is no ``TimeLimit`` wrapper inside a scan).
* ``observe(state) -> obs`` — the deterministic state→observation map,
  exposed separately so rollout scans can read the *current* obs without
  stepping (and so ``step`` need not return redundant copies).
* Observations are ``Dict[str, Array]`` matching ``observation_space``
  (a ``gym.spaces.Dict``): vectors under ``"state"`` (float32), images
  under ``"rgb"`` (uint8 ``(H, W, C)`` — the TPU-native channel-last
  layout used framework-wide).

:class:`VectorJaxEnv` batches any such env over ``num_envs`` instances with
``jax.vmap`` and implements gymnasium's SAME_STEP auto-reset semantics: when
an instance finishes, the same step returns the *reset* observation while
the true terminal observation is surfaced separately (``final_obs``) for
truncation bootstrapping — exactly the ``info["final_obs"]`` contract of
the ``AsyncVectorEnv`` path, but as traced arrays.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Obs = Dict[str, jax.Array]


class JaxEnv:
    """Base class for pure-JAX environments (see module docstring for the
    authoring contract).  Subclasses define gymnasium ``observation_space``
    / ``action_space`` (single-instance) plus the three pure functions."""

    observation_space: Any
    action_space: Any
    #: per-episode step limit driving the ``truncated`` flag (None = never)
    max_episode_steps: Optional[int] = None

    def reset(self, key: jax.Array) -> Tuple[Any, Obs]:
        raise NotImplementedError

    def step(self, state: Any, action: jax.Array) -> Tuple[Any, Obs, jax.Array, jax.Array, jax.Array]:
        raise NotImplementedError

    def observe(self, state: Any) -> Obs:
        raise NotImplementedError


class VectorJaxEnv:
    """``num_envs`` instances of a :class:`JaxEnv` as one batched pure
    function, with SAME_STEP auto-reset.

    Every method is jit-traceable; the batched ``EnvState`` pytree has
    leading dimension ``num_envs`` on every leaf and can be sharded over
    the mesh ``data`` axis (``fabric.shard_batch(state, axis=0)``) so env
    stepping parallelizes with the train step it is fused into.
    """

    def __init__(self, env: JaxEnv, num_envs: int):
        self.env = env
        self.num_envs = int(num_envs)
        self.single_observation_space = env.observation_space
        self.single_action_space = env.action_space
        self._vreset = jax.vmap(env.reset)
        self._vobserve = jax.vmap(env.observe)
        self._vstep = jax.vmap(self._autoreset_step)

    # -- single-instance auto-reset (vmapped) ------------------------------
    def _autoreset_step(self, state: Any, action: jax.Array):
        env = self.env
        s1, obs1, reward, terminated, truncated = env.step(state, action)
        done = jnp.logical_or(terminated, truncated)
        # the reset consumes a key derived from the instance's own stream —
        # split unconditionally so the trace is branch-free and the carry
        # key advances every step regardless of done
        k_reset, k_carry = jax.random.split(s1.key)
        s1 = s1._replace(key=k_carry)
        s_reset, obs_reset = env.reset(k_reset)
        if hasattr(s_reset, "level"):
            # the difficulty level rides the CARRY, not the reset: a
            # curriculum-overridden traced level (docs/population.md) must
            # survive episode boundaries, and ``env.reset`` only knows the
            # static default.  Bitwise no-op when nothing overrode it.
            s_reset = s_reset._replace(level=s1.level)
            obs_reset = env.observe(s_reset)
        s2 = jax.tree.map(lambda a, b: jnp.where(done, a, b), s_reset, s1)
        obs_out = jax.tree.map(lambda a, b: jnp.where(done, a, b), obs_reset, obs1)
        # obs1 is the TRUE final observation of the finished episode — the
        # vector-env `final_obs` contract, needed for truncation bootstraps
        return s2, obs_out, reward, terminated, truncated, obs1

    # -- batched API -------------------------------------------------------
    def reset(self, key: jax.Array) -> Tuple[Any, Obs]:
        """Batched reset: one derived key per instance."""
        return self._vreset(jax.random.split(key, self.num_envs))

    def step(self, state: Any, actions: jax.Array):
        """``(state, obs, reward, terminated, truncated, final_obs)`` —
        SAME_STEP auto-reset: finished rows come back already reset (their
        true terminal obs in ``final_obs``)."""
        return self._vstep(state, actions)

    def observe(self, state: Any) -> Obs:
        return self._vobserve(state)
