"""The token env (``envs/jax/tokens.py``): lengths, mask and reward as its docstring states them, autoreset
under ``VectorJaxEnv``, and the start inside an episode."""

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.envs.jax.core import VectorJaxEnv
from sheeprl_tpu.envs.jax.registry import make_jax_env

SMALL = dict(vocab_size=64, prompt_min=2, prompt_max=4, len_min=8, len_max=16)


def test_registered_with_integer_observations_and_a_discrete_action_per_id():
    env = make_jax_env("jax_tokens")
    assert env.action_space.n == 25024 and env.max_episode_steps == 8192 and env.never_truncates
    assert env.observation_space["tokens"].shape == (1,) and env.observation_space["tokens"].dtype == np.int32


def test_drawn_lengths_stay_within_their_bounds():
    env = make_jax_env("tokens", **SMALL)
    states, _ = jax.vmap(env.reset)(jax.random.split(jax.random.PRNGKey(0), 512))
    plen, length = np.asarray(states.prompt_len), np.asarray(states.length)
    assert plen.min() == 2 and plen.max() == 4
    assert length.min() >= 8 and length.max() <= 16 and len(set(length.tolist())) > 4
    assert np.asarray(states.prompt).min() >= 0 and np.asarray(states.prompt).max() < 64


def test_prompt_then_generation_mask_and_reward():
    """A policy that copies its prompt earns 1 at every generation step and nothing during the prompt; one
    that emits another token earns nothing; the episode terminates at its length and never truncates."""
    env = make_jax_env("tokens", **SMALL)
    state, obs = env.reset(jax.random.PRNGKey(3))
    plen, length, prompt = int(state.prompt_len), int(state.length), np.asarray(state.prompt)
    total, last_action = 0.0, None
    for t in range(length):
        assert int(env.loss_mask(state)) == int(t >= plen)
        if t < plen:
            assert int(obs["tokens"][0]) == prompt[t]
        else:
            assert int(obs["tokens"][0]) == last_action
        target = int(prompt[t % plen])
        action = target if t % 2 == 0 else (target + 1) % 64  # right on even steps, wrong on odd ones
        state, obs, reward, terminated, truncated = env.step(state, jnp.asarray(action))
        assert float(reward) == float(t >= plen and t % 2 == 0)
        assert bool(terminated) == (t == length - 1) and not bool(truncated)
        total += float(reward)
        last_action = action
    assert total == sum(1 for t in range(plen, length) if t % 2 == 0)


def test_autoreset_under_the_vector_env():
    env = make_jax_env("tokens", **SMALL)
    venv = VectorJaxEnv(env, 6)
    state, _ = venv.reset(jax.random.PRNGKey(1))
    lengths = np.asarray(state.length)
    step = jax.jit(venv.step)
    steps = np.zeros(6, np.int64)
    for _ in range(40):
        before = np.asarray(state.t)
        state, obs, _, term, trunc, _ = step(state, jnp.zeros((6,), jnp.int32))
        steps += 1
        done = np.asarray(term)
        assert not np.asarray(trunc).any()
        np.testing.assert_array_equal(done, before + 1 >= lengths)
        # a finished row comes back reset: step nought, its first prompt token as the observation, a new draw
        np.testing.assert_array_equal(np.asarray(state.t)[done], 0)
        np.testing.assert_array_equal(np.asarray(obs["tokens"])[done, 0], np.asarray(state.prompt)[done, 0])
        lengths = np.where(done, np.asarray(state.length), lengths)
    assert (steps == 40).all()


def test_a_warm_start_is_a_copier_s_episode_so_far():
    """``history`` of a warm-started state is what stepping a faultless copier from the reset would have
    observed, and the state goes on from there with the same targets."""
    env = make_jax_env("tokens", **SMALL)
    fresh, obs = env.reset(jax.random.PRNGKey(9))
    warm = env.warm_start(fresh, jax.random.PRNGKey(10))
    tokens, n = env.history(warm)
    n, plen, prompt = int(n), int(fresh.prompt_len), np.asarray(fresh.prompt)
    assert 0 <= n < int(warm.length) and env.len_min <= int(warm.length) <= env.len_max
    state, seen = fresh._replace(length=warm.length), []  # the episode the env was found in has a length of its own
    for t in range(n):
        seen.append(int(obs["tokens"][0]))
        state, obs, *_ = env.step(state, jnp.asarray(prompt[t % plen]))
    assert seen == np.asarray(tokens)[:n].tolist()
    for field in ("t", "last", "prompt_len", "length"):
        assert int(getattr(state, field)) == int(getattr(warm, field)) or (field == "last" and n == 0)
    assert int(env.observe(warm)["tokens"][0]) == int(obs["tokens"][0])


def test_warm_starts_are_the_steady_mix_of_positions():
    """Episodes drawn in proportion to their length, a uniform step of each: the share of envs found past a
    window of 2048 is the share of all steps that lie there, E[(L - 2048)+] / E[L] = 46% for log-uniform
    lengths on 1024 to 8192 (ISSUE 30's reckoning), not the 41% that a draw from the lengths' own law gives."""
    env = make_jax_env("tokens")
    fresh, _ = env.reset(jax.random.PRNGKey(0))
    warm = jax.jit(jax.vmap(lambda k: env.warm_start(fresh, k)))(jax.random.split(jax.random.PRNGKey(1), 8192))
    t, length = np.asarray(warm.t), np.asarray(warm.length)
    assert length.min() >= 1024 and length.max() <= 8192 and (t < length).all()
    lengths = np.exp(np.linspace(np.log(1024), np.log(8192), 100001))
    expected = np.clip(lengths - 2048, 0, None).mean() / lengths.mean()
    assert abs(expected - 0.46) < 0.005
    assert abs((t >= 2048).mean() - expected) < 0.02
