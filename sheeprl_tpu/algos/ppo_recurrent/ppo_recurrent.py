"""Recurrent PPO (LSTM core, or a decoder core over tokens) — coupled topology.

Capability parity with the reference
(reference: sheeprl/algos/ppo_recurrent/ppo_recurrent.py:119-524): LSTM
policy over sequences, previous-action conditioning, recurrent-state reset
on episode start, sequence-wise minibatching.

TPU-native differences:
* the reference splits rollouts at episode bounds and pads minibatches of
  variable-length sequences (reference: agent.py:237-263); here episodes
  reset INSIDE the ``lax.scan`` via the ``is_first`` mask, so training
  consumes fixed ``(T, B)`` blocks with fully static shapes — minibatches
  are subsets of the env axis;
* the whole optimization phase (forward scan, GAE, epochs × env-minibatch
  updates) is one jitted dispatch, as in the other algorithms here;
* the recurrent carry is a pytree: ``(c, h)`` for the LSTM core; attention
  caches, convolution windows and state-space states with each env's position
  for the decoder core (``algo.core: decoder``, ``exp=ppo_tokens``,
  howto/ppo_tokens.md).  The train phase re-runs a segment from the carry at
  its start for both.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

import optax

from sheeprl_tpu.algos.ppo.loss import entropy_loss, masked_mean, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.utils import actions_for_env, normalize_obs_keys, spaces_to_dims
from sheeprl_tpu.algos.ppo_recurrent.agent import LSTMCore, build_agent, build_decoder_agent, one_hot_actions
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.device_replay import stage_rollout, stage_scalar, steady_guard
from sheeprl_tpu.telemetry.spans import SPANS
from sheeprl_tpu.utils.distribution import Categorical, Normal
from sheeprl_tpu.utils.env import episode_stats, final_obs_rows, make_env, vectorize
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, flush_metrics
from sheeprl_tpu.utils.optim import build_optimizer, set_learning_rate
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import gae, normalize_tensor, polynomial_decay, save_configs


def _dist_stats(actor_out, actions, actions_dim, is_continuous):
    """Log-prob + entropy of given actions under the actor head output."""
    if is_continuous:
        mean, log_std = jnp.split(actor_out, 2, axis=-1)
        dist = Normal(mean, jnp.exp(jnp.clip(log_std, -10.0, 2.0)), event_dims=1)
        return dist.log_prob(actions), dist.entropy()
    lp, ent, start = 0.0, 0.0, 0
    for i, d in enumerate(actions_dim):
        dist = Categorical(actor_out[..., start:start + d])
        lp = lp + dist.log_prob(actions[..., i])
        ent = ent + dist.entropy()
        start += d
    return lp, ent


def _sample(actor_out, actions_dim, is_continuous, key, greedy=False):
    if is_continuous:
        mean, log_std = jnp.split(actor_out, 2, axis=-1)
        dist = Normal(mean, jnp.exp(jnp.clip(log_std, -10.0, 2.0)), event_dims=1)
        a = dist.mode() if greedy else dist.sample(key)
        return a, dist.log_prob(a)
    keys = jax.random.split(key, len(actions_dim))
    acts, lp, start = [], 0.0, 0
    for i, d in enumerate(actions_dim):
        dist = Categorical(actor_out[..., start:start + d])
        a = dist.mode() if greedy else dist.sample(keys[i])
        acts.append(a)
        lp = lp + dist.log_prob(a)
        start += d
    return jnp.stack(acts, axis=-1).astype(jnp.float32), lp


def _warm_start(fabric: Any, cfg: Any, venv: Any, core: Any, params: Any, actor: Dict[str, Any], key: jax.Array):
    """A run that starts where a long run finds its envs (an env with ``warm_start`` and ``history``): every env
    moved to a drawn step of an episode, and the core's carry filled from the episode so far, a chunk of tokens
    at a time through the segment pass."""
    n_envs = venv.num_envs
    env_state = jax.jit(jax.vmap(venv.env.warm_start))(
        actor["env"], jax.random.split(key, n_envs)
    )
    tokens, n = jax.jit(jax.vmap(venv.env.history))(env_state)
    chunk = min(int(cfg.algo.rollout_steps), core.prefill_chunk)
    prefill = fabric.compile(core.prefill, name=f"{cfg.algo.name}.prefill", donate_argnums=(1,))
    carry = actor["carry"]
    for start in range(0, int(np.asarray(n).max()), chunk):
        tok = jax.lax.dynamic_slice_in_dim(tokens, start, chunk, axis=1).T
        carry = prefill(params, carry, tok, jnp.clip(n - start, 0, chunk))
    return {**actor, "env": env_state, "carry": carry, "is_first": (n == 0).astype(jnp.float32)[:, None]}


@register_algorithm()
def main(fabric: Any, cfg: Any) -> None:
    if cfg.buffer.get("share_data", False):
        import warnings

        warnings.warn(
            "buffer.share_data=True: with recurrent PPO only gradients are "
            "shared — per-env hidden-state sequences stay on their process "
            "(reference: sheeprl/algos/ppo_recurrent/ppo_recurrent.py:132-135)"
        )
    rank = fabric.global_rank
    key = fabric.seed_everything(cfg.seed)

    log_dir = get_log_dir(fabric, cfg.root_dir, cfg.run_name, base=cfg.get("log_dir", "logs/runs"))
    logger = get_logger(fabric, cfg, log_dir)
    ckpt_mgr = fabric.get_checkpoint_manager(cfg, log_dir)
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    num_envs = cfg.env.num_envs
    from sheeprl_tpu.envs.jax.registry import anakin_enabled

    use_anakin = anakin_enabled(cfg, fabric)
    if use_anakin:
        # Anakin mode (envs/jax/anakin.py): the env lives INSIDE the
        # compiled update — no vector-env processes exist at all
        from sheeprl_tpu.envs.jax.core import VectorJaxEnv
        from sheeprl_tpu.envs.jax.registry import jax_env_from_cfg

        envs = None
        with SPANS.setup_span("setup.env"):
            venv = VectorJaxEnv(jax_env_from_cfg(cfg), num_envs)
        obs_space = venv.single_observation_space
        act_space = venv.single_action_space
    else:
        envs = vectorize(
            cfg,
            [
                make_env(cfg, cfg.seed + rank * num_envs + i, rank, run_name=log_dir, vector_env_idx=i)
                for i in range(num_envs)
            ],
        )
        obs_space = envs.single_observation_space
        act_space = envs.single_action_space
    normalize_obs_keys(cfg, obs_space)
    actions_dim, is_continuous = spaces_to_dims(act_space)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)

    state: Dict[str, Any] = {}
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)
    if state and state.get("key") is not None:
        # resume the train-dispatch RNG stream bit-exactly (rank-identical)
        key = jnp.asarray(state["key"])
    # the one place that knows the cores apart: `algo.core` selects a model as `algo=` does, and from here on
    # the loop drives `core` through the calls `agent.LSTMCore` lists
    kind = str(cfg.algo.get("core", "lstm"))
    if kind == "decoder":
        if not use_anakin or cfg.algo.run_test:
            raise ValueError(
                "algo.core=decoder runs on the fused path: it needs a pure-JAX env (env=jax_*) and "
                "algo.run_test=False (the test plays a host episode)"
            )
        with SPANS.setup_span("setup.agent"):
            agent, params = build_decoder_agent(
                fabric, cfg, act_space, int(venv.env.max_episode_steps), state.get("agent")
            )
        core = agent
    elif kind == "lstm":
        with SPANS.setup_span("setup.agent"):
            agent, params = build_agent(fabric, actions_dim, is_continuous, cfg, obs_space, state.get("agent"))
        core = LSTMCore(agent)
    else:
        raise ValueError(f"Unknown algo.core '{kind}'; options: lstm, decoder")
    Agent = type(agent)
    act_width = core.prev_action_width
    with SPANS.setup_span("setup.optimizer"):
        optimizer = build_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm)
        # made in place: a copy of Adam's state beside its source does not fit beside a model that fills the chip
        opt_state = fabric.replicate(state["opt_state"]) if state.get("opt_state") else jax.jit(
            optimizer.init, out_shardings=fabric.replicated
        )(params)

    aggregator = MetricAggregator(cfg.metric.aggregator.metrics if cfg.metric.log_level > 0 else {})
    timer.configure(cfg.metric)

    # on-policy loops honor algo.player.device (placement only; the sync
    # cadence options are meaningless on-policy: rollouts must use the
    # current weights)
    host = fabric.player_device(cfg)
    gamma = float(cfg.algo.gamma)
    gae_lambda = float(cfg.algo.gae_lambda)
    vf_coef = float(cfg.algo.vf_coef)
    initial_ent_coef = float(cfg.algo.ent_coef)
    ent_coef_v = initial_ent_coef
    clip_coef = float(cfg.algo.clip_coef)
    clip_vloss = bool(cfg.algo.clip_vloss)
    normalize_adv = bool(cfg.algo.normalize_advantages)
    base_lr = float(cfg.algo.optimizer.lr)
    reduction = cfg.algo.loss_reduction
    update_epochs = int(cfg.algo.update_epochs)

    def policy_step_fn(p, carry, obs, prev_actions, is_first, k):
        # key advances INSIDE the jitted step (one host dispatch per env step)
        k_sample, k_next = jax.random.split(k)
        carry, (actor_out, value) = core.policy_step(p, carry, obs, prev_actions, is_first)
        actions, logprob = _sample(actor_out, actions_dim, is_continuous, k_sample)
        return carry, actions, logprob, value[..., 0], k_next

    # compile-once routing: AOT-compiled per abstract signature, counted by
    # the recompile detector (parallel/compile.py)
    policy_step_fn = fabric.compile(
        policy_step_fn,
        name=f"{cfg.algo.name}.policy_step",
        max_recompiles=cfg.algo.get("max_recompiles"),
    )

    def train_phase(p, o_state, rollout, init_carry, last_values, k, ent_coef, env_bs, num_minibatches):
        """Forward scan + GAE + epochs of env-axis minibatch updates."""
        T, B = rollout["rewards"].shape
        mask = rollout.get("mask")  # 1 where a step counts in the losses; an env without one: every step

        def fwd(p, env_idx):
            with jax.named_scope("update.gather"):
                obs = {kk: jnp.take(rollout[kk], env_idx, axis=1) for kk in mlp_keys}
                prev_a = jnp.take(rollout["prev_actions"], env_idx, axis=1)
                first = jnp.take(rollout["is_first"], env_idx, axis=1)
                carry = jax.tree.map(lambda x: jnp.take(x, env_idx, axis=0), init_carry)
            return core.policy_segment(p, obs, prev_a, first, carry)

        with jax.named_scope("gae"):  # the value pass over the whole rollout included
            if "values" in rollout:  # graftlint: disable=trace-python-branch  (a key of the dict, not a value: the rollout kept its own values, so no second pass over every token)
                values = rollout["values"]
            else:
                _, values, _, _, _ = fwd(p, jnp.arange(B))
                values = values[..., 0]
            returns, advantages = gae(
                rollout["rewards"], values, rollout["dones"], last_values, gamma, gae_lambda
            )

        def epoch_body(carry, key_e):
            p, o_state, aux = carry
            perm = jax.random.permutation(key_e, B)
            pad = num_minibatches * env_bs - B
            perm = jnp.concatenate([perm, perm[: max(pad, 0)]]) if pad > 0 else perm

            def mb_body(i, carry2):
                p, o_state, _, aux = carry2
                env_idx = jax.lax.dynamic_slice(perm, (i * env_bs,), (env_bs,))

                @jax.named_scope("update.loss")
                def loss_of(p_):
                    a_out, new_values, load, own_loss, read = fwd(p_, env_idx)
                    acts = jnp.take(rollout["actions"], env_idx, axis=1)
                    lp, ent = _dist_stats(a_out, acts, actions_dim, is_continuous)
                    adv = jnp.take(advantages, env_idx, axis=1)
                    old_lp = jnp.take(rollout["logprobs"], env_idx, axis=1)
                    ret = jnp.take(returns, env_idx, axis=1)
                    old_v = jnp.take(values, env_idx, axis=1)
                    if mask is None:
                        if normalize_adv:
                            adv = normalize_tensor(adv)
                        pg = policy_loss(lp, old_lp, adv, clip_coef, reduction)
                        vl = value_loss(new_values[..., 0], old_v, ret, clip_coef, clip_vloss, reduction)
                        el = entropy_loss(ent, reduction)
                    else:
                        mk = jnp.take(mask, env_idx, axis=1)
                        if normalize_adv:
                            adv = normalize_tensor(adv, mask=mk)
                        pg = policy_loss(lp, old_lp, adv, clip_coef, reduction, mk)
                        vl = value_loss(new_values[..., 0], old_v, ret, clip_coef, clip_vloss, reduction, mk)
                        el = entropy_loss(ent, reduction, mk)
                    loss = pg + vf_coef * vl + ent_coef * el
                    if own_loss is not None:  # the core's own term (a sparse decoder's L_I), averaged as PPO's are
                        with jax.named_scope("policy.attn.index_loss"):
                            loss = loss + (jnp.mean(own_loss) if mask is None else masked_mean(own_loss, mk))
                    return loss, ((pg, vl, el), load, read)

                (_, ((pg, vl, el), load, read)), grads = jax.value_and_grad(loss_of, has_aux=True)(p)
                with jax.named_scope("update.optim"):
                    updates, o_state = optimizer.update(grads, o_state, p)
                    p = optax.apply_updates(p, updates)
                    if load is not None:
                        p = core.after_update(p, load)
                        first = aux["updates"] == 0
                        aux = {
                            **aux,
                            "updates": aux["updates"] + 1,
                            "load": aux["load"] + load,
                            "first_load": jnp.where(first, load, aux["first_load"]),
                            "first_losses": jnp.where(first, jnp.stack([pg, vl, el]), aux["first_losses"]),
                            "moe_rows_run": aux["moe_rows_run"] + core.rows_run(load, T * env_bs),
                        }
                        if read is not None:  # the key blocks a sparse layer's kernels read, and those held
                            aux["segment_blocks"] = aux["segment_blocks"] + read
                return p, o_state, (pg, vl, el), aux

            p, o_state, losses, aux = jax.lax.fori_loop(
                0, num_minibatches, mb_body,
                (p, o_state, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())), aux),
            )
            return (p, o_state, aux), losses

        # recurrent PPO is MLP-only (no conv trunk): the XLA-CPU
        # outlined-loop penalty is conv-specific (utils.window_scan), so the
        # compact scan/fori lowering stays unconditionally
        (p, o_state, aux), losses = jax.lax.scan(
            epoch_body, (p, o_state, core.init_aux()), jax.random.split(k, update_epochs)
        )
        return p, o_state, jax.tree.map(lambda x: x[-1], losses), aux

    # the staged rollout is donated too (argnum 2): one dispatch consumes it
    # exactly once (see ppo.py)
    train_phase_fn = train_phase  # raw callable: the Anakin path fuses it
    train_phase = fabric.compile(
        train_phase,
        name=f"{cfg.algo.name}.train_phase",
        donate_argnums=(0, 1, 2),
        static_argnames=("env_bs", "num_minibatches"),
        max_recompiles=cfg.algo.get("max_recompiles"),
    )
    guard_on = bool(cfg.buffer.get("transfer_guard", False))

    # ---------------- counters ----------------------------------------------
    rollout_steps = int(cfg.algo.rollout_steps)
    # GLOBAL env-step accounting: every process steps its own envs
    policy_steps_per_iter = num_envs * rollout_steps * fabric.num_processes
    total_iters = max(int(cfg.algo.total_steps) // policy_steps_per_iter, 1)
    if cfg.dry_run:
        total_iters = 1
    start_iter = int(state.get("update", 0)) + 1 if state else 1
    policy_step = int(state.get("policy_step", 0))
    last_log = int(state.get("last_log", 0))
    last_checkpoint = int(state.get("last_checkpoint", 0))

    rb = ReplayBuffer(rollout_steps, num_envs, memmap=False, obs_keys=mlp_keys) if not use_anakin else None

    hidden_size = int(cfg.algo.rnn.lstm.hidden_size)
    if not use_anakin:
        # rank-offset: each process's envs must be distinct streams or
        # multi-host DP collects the same data num_processes times
        obs, _ = envs.reset(seed=cfg.seed + rank * num_envs)
        prev_actions = np.zeros((num_envs, act_width), np.float32)
        is_first = np.ones((num_envs, 1), np.float32)
        carry_np = (
            np.zeros((num_envs, hidden_size), np.float32),
            np.zeros((num_envs, hidden_size), np.float32),
        )
    player_params = None if use_anakin else fabric.to_host(params)  # the fused path has no host player
    last_losses = None
    # per-rank player key stream, advanced inside policy_step_fn; the main
    # `key` stays rank-identical for train dispatches
    player_key = jax.device_put(
        # resume this rank's player RNG stream bit-exactly when saved
        jnp.asarray(state["player_key"]) if state and state.get("player_key") is not None
        else jax.random.fold_in(key, rank),
        host,
    )

    # the train phase is a GLOBAL program: under multi-host the env axis is
    # the concatenation of every process's local envs.  Single-process keeps
    # the replicated layout (env-axis minibatch gathers are cheapest there),
    # so sharding kicks in only across processes.
    sharded_envs = fabric.num_processes > 1
    if sharded_envs:
        fabric.env_sharding_plan(num_envs, "recurrent PPO")  # fail fast
    global_envs = num_envs * (fabric.num_processes if sharded_envs else 1)
    env_bs = max(
        1,
        min(global_envs, (int(cfg.algo.per_rank_batch_size) * fabric.world_size) // rollout_steps),
    )
    num_minibatches = -(-global_envs // env_bs)

    # ---------------- Anakin fused rollout+train ----------------------------
    if use_anakin:
        from sheeprl_tpu.envs.jax.anakin import (
            init_actor_state,
            make_recurrent_rollout_fn,
            traced_polynomial_decay,
        )

        def _sample_fn(actor_out, k):
            return _sample(actor_out, actions_dim, is_continuous, k)

        rollout_fn = make_recurrent_rollout_fn(
            venv, core.policy_step, _sample_fn, core.encode_prev,
            mlp_keys=mlp_keys, action_space=act_space, gamma=gamma,
            rollout_steps=rollout_steps, store_values=core.stores_values,
        )

        def anakin_phase(p, o_state, actor, k):
            """``nn.scan``-policy rollout + forward scan + GAE + epochs in
            ONE device program, schedules computed in-trace from the
            donated update counter (zero H2D in steady state — the
            ppo/a2c Anakin gates, ROADMAP item 5)."""
            k_roll, k_train, k_next = jax.random.split(k, 3)
            step0 = actor["update"]
            ent = (
                traced_polynomial_decay(step0, initial=initial_ent_coef, max_decay_steps=total_iters)
                if cfg.algo.anneal_ent_coef
                else jnp.float32(initial_ent_coef)
            )
            if cfg.algo.anneal_lr:
                o_state = set_learning_rate(
                    o_state,
                    traced_polynomial_decay(step0, initial=base_lr, max_decay_steps=total_iters),
                )
            actor, rollout, init_carry, last_values, stats = rollout_fn(core.acting_params(p), actor, k_roll)
            stats = {**stats, **core.rollout_stats(rollout, init_carry, venv, actor)}
            p, o_state, losses, aux = train_phase_fn(
                p, o_state, rollout, init_carry, last_values, k_train, ent,
                env_bs=env_bs, num_minibatches=num_minibatches,
            )
            if aux is not None:
                told = ("load", "first_load", "first_losses", "moe_rows_run", "segment_blocks")
                stats = {**stats, **{kk: aux[kk] for kk in told if kk in aux}}
            return p, o_state, actor, k_next, losses, stats

        anakin_step = fabric.compile(
            anakin_phase,
            name=f"{cfg.algo.name}.anakin_phase",
            donate_argnums=(0, 1, 2),
            max_recompiles=cfg.algo.get("max_recompiles"),
        )
        with SPANS.setup_span("setup.env"):  # the envs' first reset, and the carry they start from
            actor_state = init_actor_state(
                fabric, venv, jax.random.fold_in(key, fabric.global_rank + 1),
                start_iter - 1,
                sharded=num_envs % fabric.local_world_size == 0,
                extra={
                    "carry": core.initial_state(num_envs),
                    "prev_actions": jnp.zeros((num_envs, act_width), jnp.float32),
                    "is_first": jnp.ones((num_envs, 1), jnp.float32),
                },
            )
        if hasattr(venv.env, "warm_start") and hasattr(core, "prefill"):
            with SPANS.setup_span("setup.prefill"):
                actor_state = _warm_start(fabric, cfg, venv, core, params, actor_state, jax.random.fold_in(key, 7))
    guard_anakin = bool(cfg.buffer.get("transfer_guard", False))

    from sheeprl_tpu.utils.profiler import ProfilerGate

    profiler = ProfilerGate(cfg, log_dir)
    for update in range(start_iter, total_iters + 1):
        profiler.step(update)
        SPANS.iteration(update)  # the `iter` span: closes the one before
        if use_anakin:
            # -------- fused rollout+train: ONE dispatch per update ---------
            with timer("Time/train_time"):
                with steady_guard(guard_anakin and update > start_iter):
                    params, opt_state, actor_state, key, last_losses, ep_stats = anakin_step(
                        params, opt_state, actor_state, key
                    )
                policy_step += num_envs * rollout_steps * fabric.num_processes
            if cfg.metric.log_level > 0:
                # completion arrays are tiny; the pull is D2H (legal under
                # the H2D-scoped steady guard)
                from sheeprl_tpu.envs.jax.anakin import episode_stats_from_device

                # the loop's first wait for the fused dispatch: its host time
                # is the device's, so it gets a span of its own
                with SPANS.span("stats.pull", phase=False) as pull:
                    rets, lens = episode_stats_from_device(ep_stats)
                    counts = core.host_counts(ep_stats) if pull is not None else {}
                    if counts:
                        pull.count(**counts)
                for ep_ret, ep_len in zip(rets, lens):
                    aggregator.update("Rewards/rew_avg", float(ep_ret))
                    aggregator.update("Game/ep_len_avg", int(ep_len))
        else:
            init_carry = (carry_np[0].copy(), carry_np[1].copy())
            with timer("Time/env_interaction_time"):
                with jax.default_device(host):
                    for _ in range(rollout_steps):
                        policy_step += num_envs * fabric.num_processes
                        dev_obs = {
                            k: jnp.asarray(np.asarray(obs[k], np.float32).reshape(num_envs, -1))
                            for k in mlp_keys
                        }
                        carry, actions, logprobs, _, player_key = policy_step_fn(
                            player_params,
                            (jnp.asarray(carry_np[0]), jnp.asarray(carry_np[1])),
                            dev_obs,
                            jnp.asarray(prev_actions),
                            jnp.asarray(is_first),
                            player_key,
                        )
                        carry_np = (np.asarray(carry[0]), np.asarray(carry[1]))
                        actions_np = np.asarray(actions)
                        next_obs, rewards, terminated, truncated, info = envs.step(
                            actions_for_env(actions_np, act_space)
                        )
                        dones = np.logical_or(terminated, truncated).astype(np.float32)
                        rewards = np.asarray(rewards, np.float32)

                        # truncation bootstrap (reference: ppo.py:287-306) using the
                        # post-step recurrent state; padded to the full env batch
                        if np.any(truncated):
                            final_obs = final_obs_rows(info, np.nonzero(truncated)[0], mlp_keys)
                            if final_obs is not None:
                                padded = {
                                    k: np.asarray(next_obs[k], np.float32).reshape(num_envs, -1).copy()
                                    for k in mlp_keys
                                }
                                for k in mlp_keys:
                                    padded[k][truncated] = np.asarray(final_obs[k], np.float32).reshape(
                                        int(truncated.sum()), -1
                                    )
                                prev_a_boot = np.asarray(
                                    one_hot_actions(jnp.asarray(actions_np), actions_dim, is_continuous)
                                )
                                _, (_, v_boot) = agent.apply(
                                    player_params, method=Agent.step,
                                    carry=(jnp.asarray(carry_np[0]), jnp.asarray(carry_np[1])),
                                    obs={k: jnp.asarray(padded[k]) for k in mlp_keys},
                                    prev_actions=jnp.asarray(prev_a_boot),
                                    is_first=jnp.zeros((num_envs, 1)),
                                )
                                v_boot = np.asarray(v_boot)[..., 0]
                                rewards[truncated] += gamma * v_boot[truncated]

                        step = {
                            "actions": actions_np[None],
                            "logprobs": np.asarray(logprobs)[None],
                            "rewards": rewards[None],
                            "dones": dones[None],
                            "is_first": is_first[None, :, 0],
                            "prev_actions": prev_actions[None],
                        }
                        for k in mlp_keys:
                            step[k] = np.asarray(obs[k], np.float32).reshape(1, num_envs, -1)
                        rb.add({k: v[..., None] if v.ndim == 2 else v for k, v in step.items()})

                        obs = next_obs
                        prev_actions = np.array(
                            one_hot_actions(jnp.asarray(actions_np), actions_dim, is_continuous)
                        )
                        prev_actions[dones.astype(bool)] = 0.0
                        is_first = dones[:, None]
                        for ep_ret, ep_len in episode_stats(info):
                            aggregator.update("Rewards/rew_avg", ep_ret)
                            aggregator.update("Game/ep_len_avg", ep_len)

            with timer("Time/train_time"):
                # donated device staging: host-numpy layout + EXPLICIT device_puts
                # (data/device_replay.stage_rollout), rollout donated into the
                # one-dispatch update (see ppo.py)
                local = rb.buffer
                host_rollout = {k: np.asarray(local[k], np.float32) for k in mlp_keys}
                host_rollout["actions"] = np.asarray(local["actions"])
                host_rollout["prev_actions"] = np.asarray(local["prev_actions"])
                host_rollout["logprobs"] = np.asarray(local["logprobs"][..., 0])
                host_rollout["rewards"] = np.asarray(local["rewards"][..., 0])
                host_rollout["dones"] = np.asarray(local["dones"][..., 0])
                host_rollout["is_first"] = np.asarray(local["is_first"])  # (T, B, 1)
                # single-process: replicate (the env-axis minibatch gathers are
                # cheapest on replicated data); multi-host: each process only has
                # its own env rows, so assemble the global env axis instead
                rollout = stage_rollout(fabric, host_rollout, axis=1, sharded=sharded_envs)

                # bootstrap values for the state after the rollout
                dev_obs = {
                    k: jnp.asarray(np.asarray(obs[k], np.float32).reshape(num_envs, -1)) for k in mlp_keys
                }
                _, (_, last_v) = agent.apply(
                    player_params, method=Agent.step,
                    carry=(jnp.asarray(carry_np[0]), jnp.asarray(carry_np[1])),
                    obs=dev_obs, prev_actions=jnp.asarray(prev_actions),
                    is_first=jnp.asarray(is_first),
                )
                key, tk = jax.random.split(key)
                carry_pair = (np.asarray(init_carry[0]), np.asarray(init_carry[1]))
                last_v_flat = np.asarray(last_v)[..., 0]
                ent_dev = stage_scalar(ent_coef_v)
                with steady_guard(guard_on and update > start_iter):
                    params, opt_state, last_losses, _ = train_phase(
                        params, opt_state, rollout,
                        fabric.shard_batch(carry_pair, axis=0) if sharded_envs else fabric.replicate(carry_pair),
                        fabric.shard_batch(last_v_flat, axis=0) if sharded_envs else fabric.replicate(last_v_flat),
                        tk, ent_dev, env_bs=env_bs, num_minibatches=num_minibatches,
                    )
                player_params = fabric.to_host(params)

        # (Anakin mode anneals in-trace from the donated update counter —
        # host-side schedule state would be a per-update H2D transfer)
        if cfg.algo.anneal_lr and not use_anakin:
            opt_state = set_learning_rate(
                opt_state,
                polynomial_decay(update, initial=base_lr, final=0.0, max_decay_steps=total_iters),
            )
        if cfg.algo.anneal_ent_coef and not use_anakin:
            ent_coef_v = polynomial_decay(
                update, initial=initial_ent_coef, final=0.0, max_decay_steps=total_iters
            )

        if cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or update == total_iters or cfg.dry_run
        ):
            if last_losses is not None:
                pg, vl, el = last_losses
                aggregator.update("Loss/policy_loss", pg)
                aggregator.update("Loss/value_loss", vl)
                aggregator.update("Loss/entropy_loss", el)
            last_log = flush_metrics(aggregator, timer, logger, policy_step, last_log)

        if ckpt_mgr.should_save(policy_step, last_checkpoint, final=update == total_iters):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": params,
                "opt_state": opt_state,
                "key": key,
                "player_key": player_key,
                "update": update,
                "policy_step": policy_step,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_{rank}.ckpt"),
                state=ckpt_state,
            )
        if ckpt_mgr.preempted:
            fabric.print(f"Preemption: committed checkpoint at step {policy_step}, exiting")
            break

    SPANS.end_iteration()
    profiler.close()
    if envs is not None:
        envs.close()
    ckpt_mgr.finalize()
    if fabric.is_global_zero and cfg.algo.run_test and not ckpt_mgr.preempted:
        from sheeprl_tpu.algos.ppo_recurrent.utils import test

        if use_anakin:
            # the fused path never maintained a host player copy
            player_params = fabric.to_host(params)
        test(agent, player_params, cfg, log_dir, logger)
    if logger is not None:
        logger.close()
