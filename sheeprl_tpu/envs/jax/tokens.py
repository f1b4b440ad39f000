"""A token environment: generation against a reward that can be checked.

An episode draws a prompt of ``prompt_min`` to ``prompt_max`` tokens (uniform over
the vocabulary's ``vocab_size`` ids) and a total length ``L``, log-uniform on
``len_min`` to ``len_max``.  For the first ``len(prompt)`` steps the observation
is the next prompt token, the action is ignored, the reward is 0 and the step's
``loss_mask`` is 0.  After that the observation is the token the policy emitted
last, the target at step ``t`` is ``prompt[t mod len(prompt)]`` (the policy is
to copy its prompt over and over), the reward is 1 where the action equals it,
and ``loss_mask`` is 1.  The episode terminates at ``L``; it never truncates
(``never_truncates``: the fused rollout skips its bootstrap pass for such an env).

Observations are integers under ``"tokens"``; the action space is
``Discrete(vocab_size)``.  ``warm_start``/``history`` put a fresh instance
where a long run would find it: in an episode drawn in proportion to its
length, at a uniform step of it, with the episode so far as a policy that
copied without a fault would have left it.  The recurrent PPO loop starts a
run that way where its core can fill its carry from the history (the decoder's
caches): in the steady mix of positions, not with every env at nought, where
no minibatch holds a position past 256 x the dispatches so far.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from gymnasium import spaces

from sheeprl_tpu.envs.jax.core import JaxEnv, Obs


class TokensState(NamedTuple):
    prompt: jax.Array  # (prompt_max,) int32, the first prompt_len are the prompt
    prompt_len: jax.Array
    length: jax.Array  # L: the step at which the episode terminates
    t: jax.Array  # steps taken
    last: jax.Array  # the token the policy emitted last
    key: jax.Array


class JaxTokens(JaxEnv):
    never_truncates = True

    def __init__(
        self, vocab_size: int = 25024, prompt_min: int = 32, prompt_max: int = 128,
        len_min: int = 1024, len_max: int = 8192,
    ):
        if not (0 < prompt_min <= prompt_max < len_min <= len_max):
            raise ValueError("need 0 < prompt_min <= prompt_max < len_min <= len_max")
        self.vocab_size, self.prompt_min, self.prompt_max = int(vocab_size), int(prompt_min), int(prompt_max)
        self.len_min, self.len_max = int(len_min), int(len_max)
        self.max_episode_steps = self.len_max
        self.observation_space = spaces.Dict(
            {"tokens": spaces.Box(0, self.vocab_size - 1, (1,), dtype=np.int32)}
        )
        self.action_space = spaces.Discrete(self.vocab_size)

    def reset(self, key: jax.Array) -> Tuple[TokensState, Obs]:
        k_prompt, k_plen, k_len, k_carry = jax.random.split(key, 4)
        log_len = jax.random.uniform(k_len, (), minval=math.log(self.len_min), maxval=math.log(self.len_max))
        state = TokensState(
            prompt=jax.random.randint(k_prompt, (self.prompt_max,), 0, self.vocab_size, dtype=jnp.int32),
            prompt_len=jax.random.randint(k_plen, (), self.prompt_min, self.prompt_max + 1, dtype=jnp.int32),
            length=jnp.clip(jnp.exp(log_len).astype(jnp.int32), self.len_min, self.len_max),
            t=jnp.zeros((), jnp.int32),
            last=jnp.zeros((), jnp.int32),
            key=k_carry,
        )
        return state, self.observe(state)

    def observe(self, state: TokensState) -> Obs:
        in_prompt = state.t < state.prompt_len
        token = jnp.where(in_prompt, state.prompt[jnp.minimum(state.t, self.prompt_max - 1)], state.last)
        return {"tokens": token.astype(jnp.int32)[None]}

    def loss_mask(self, state: TokensState) -> jax.Array:
        """1 where the step about to be taken is one of generation (its action counts)."""
        return (state.t >= state.prompt_len).astype(jnp.float32)

    def step(self, state: TokensState, action: jax.Array):
        action = action.astype(jnp.int32)
        target = state.prompt[jnp.mod(state.t, state.prompt_len)]
        reward = jnp.where((state.t >= state.prompt_len) & (action == target), 1.0, 0.0).astype(jnp.float32)
        new = state._replace(t=state.t + 1, last=action)
        terminated = new.t >= state.length
        return new, self.observe(new), reward, terminated, jnp.zeros((), bool)

    # -- a start inside the episode ------------------------------------------
    def history(self, state: TokensState) -> Tuple[jax.Array, jax.Array]:
        """``(len_max,)`` observations of steps 0 .. t-1 as a faultless copier leaves them, and ``t``."""
        i = jnp.arange(self.len_max, dtype=jnp.int32)
        copied = state.prompt[jnp.mod(jnp.maximum(i - 1, 0), state.prompt_len)]
        return jnp.where(i < state.prompt_len, state.prompt[jnp.minimum(i, self.prompt_max - 1)], copied), state.t

    def warm_start(self, state: TokensState, key: jax.Array) -> TokensState:
        """A fresh instance moved to where a long run finds an env: in an episode drawn in proportion to its
        length (uniform on ``len_min`` to ``len_max``, since a log-uniform length has density 1/L), at a step
        drawn uniformly from it."""
        k_len, k_t = jax.random.split(key)
        length = jax.random.randint(k_len, (), self.len_min, self.len_max + 1, dtype=jnp.int32)
        moved = state._replace(length=length, t=jax.random.randint(k_t, (), 0, length, dtype=jnp.int32))
        return moved._replace(last=self.history(moved)[0][jnp.minimum(moved.t, self.len_max - 1)])
