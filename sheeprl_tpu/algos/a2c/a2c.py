"""A2C, coupled topology.

Capability parity with the reference (reference: sheeprl/algos/a2c/a2c.py:117-440):
on-policy rollouts, GAE, one synchronized gradient step per rollout.

The reference accumulates gradients across minibatches under
``fabric.no_backward_sync`` so DDP all-reduces once per update
(reference: a2c.py:53-116).  Gradient accumulation is a workaround for
framework overhead, not an algorithmic feature — on TPU the mathematically
identical thing is ONE jitted full-batch update per rollout (summed losses,
single XLA-inserted gradient all-reduce), which is also the fastest mapping
to the MXU.  Agent/encoder/player machinery is shared with PPO
(sheeprl_tpu/algos/ppo/agent.py) — same module family in the reference too.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.a2c.loss import policy_loss, value_loss
from sheeprl_tpu.algos.ppo.agent import build_agent, evaluate_actions, sample_actions
from sheeprl_tpu.algos.ppo.utils import (
    actions_for_env,
    normalize_obs_keys,
    obs_to_np,
    prepare_obs,
    spaces_to_dims,
    test,
)
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.device_replay import stage_rollout, steady_guard
from sheeprl_tpu.envs.jax.anakin import read_obs_fn
from sheeprl_tpu.envs.jax.registry import anakin_enabled
from sheeprl_tpu.utils.env import episode_stats, final_obs_rows, make_env, vectorize
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, flush_metrics
from sheeprl_tpu.utils.optim import build_optimizer, set_learning_rate
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import gae, polynomial_decay, save_configs


@register_algorithm()
def main(fabric: Any, cfg: Any) -> None:
    rank = fabric.global_rank
    key = fabric.seed_everything(cfg.seed)

    log_dir = get_log_dir(fabric, cfg.root_dir, cfg.run_name, base=cfg.get("log_dir", "logs/runs"))
    logger = get_logger(fabric, cfg, log_dir)
    ckpt_mgr = fabric.get_checkpoint_manager(cfg, log_dir)
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    num_envs = cfg.env.num_envs
    use_anakin = anakin_enabled(cfg, fabric)
    # population mode (docs/population.md): vmap whole agents over a
    # population axis INSIDE the fused Anakin executable, with in-trace PBT
    pop_size = int(cfg.get("population", {}).get("size", 0) or 0)
    use_population = pop_size > 1
    if use_population and not use_anakin:
        raise ValueError(
            "population.size>1 rides the Anakin axis: it needs a pure-JAX env "
            "(env=jax_*), algo.anakin != False, and a single-process run"
        )
    if use_anakin:
        # Anakin mode (envs/jax/anakin.py): the env lives INSIDE the
        # compiled update — no vector-env processes exist at all
        from sheeprl_tpu.envs.jax.core import VectorJaxEnv
        from sheeprl_tpu.envs.jax.registry import jax_env_from_cfg

        envs = None
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
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys
    read_obs = read_obs_fn(cnn_keys, obs_space)
    dist_type = cfg.get("distribution", {}).get("type", "auto")

    state: Dict[str, Any] = {}
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)
    if state and state.get("key") is not None:
        # resume the train-dispatch RNG stream bit-exactly (rank-identical)
        key = jnp.asarray(state["key"])
    agent, params = build_agent(
        fabric, actions_dim, is_continuous, cfg, obs_space,
        # population checkpoints hold STACKED (P, ...) params — restored in
        # the population block below, not through the single-agent loader
        None if (use_population and state) else state.get("agent"),
    )
    optimizer = build_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm)
    if use_population:
        opt_state = None  # stacked per-member init happens in the population block
    else:
        opt_state = fabric.replicate(state.get("opt_state") or optimizer.init(params))

    aggregator = MetricAggregator(
        cfg.metric.aggregator.metrics if cfg.metric.log_level > 0 else {}
    )
    timer.configure(cfg.metric)

    # on-policy loops honor algo.player.device (placement only; the sync
    # cadence options are meaningless on-policy: rollouts must use the
    # current weights)
    host = fabric.player_device(cfg)
    reduction = cfg.algo.loss_reduction
    vf_coef = float(cfg.algo.vf_coef)
    ent_coef = float(cfg.algo.ent_coef)
    gamma = float(cfg.algo.gamma)
    gae_lambda = float(cfg.algo.gae_lambda)

    def policy_step_fn(p, obs, k):
        # key advances INSIDE the jitted step: one dispatch per env step
        # instead of three (split + fold_in used to run as separate host
        # programs — measurable at A2C's rollout_steps=5 granularity)
        k_sample, k_next = jax.random.split(k)
        out, value = agent.apply(p, obs)
        actions, logprob, _ = sample_actions(out, actions_dim, is_continuous, k_sample, dist_type=dist_type)
        return actions, logprob, value[..., 0], k_next

    # compile-once routing: AOT-compiled per abstract signature, counted by
    # the recompile detector (parallel/compile.py)
    policy_step_fn = fabric.compile(
        policy_step_fn,
        name=f"{cfg.algo.name}.policy_step",
        max_recompiles=cfg.algo.get("max_recompiles"),
    )

    @jax.jit
    def values_fn(p, obs):
        _, value = agent.apply(p, obs)
        return value[..., 0]

    player_params = fabric.to_host(params)

    def train_phase(p, o_state, rollout, last_obs, traced_ent_coef=None):
        """GAE + one full-batch gradient step, in one device program.

        ``traced_ent_coef`` lets the population path pass the entropy
        coefficient as per-member traced data (hyperparameters-as-data,
        docs/population.md); ``None`` (the compiled single-agent signature)
        bakes in the static config value."""
        e_coef = ent_coef if traced_ent_coef is None else traced_ent_coef
        T, B = rollout["rewards"].shape
        # a fused rollout's uint8 pixel leaves back to float frames; a rollout
        # staged from the host passes through (envs/jax/anakin.py)
        flat_obs = read_obs({k: rollout[k].reshape((T * B,) + rollout[k].shape[2:]) for k in obs_keys})
        _, values0 = agent.apply(p, flat_obs)
        values0 = values0[..., 0].reshape(T, B)
        next_value = values_fn(p, last_obs)
        returns, advantages = gae(
            rollout["rewards"], values0, rollout["dones"], next_value, gamma, gae_lambda
        )

        def loss_fn(p):
            out, new_values = agent.apply(p, flat_obs)
            lp, ent = evaluate_actions(
                out, rollout["actions"].reshape(T * B, -1), actions_dim, is_continuous, dist_type=dist_type
            )
            pg = policy_loss(lp, advantages.reshape(-1), reduction)
            vl = value_loss(new_values[..., 0], returns.reshape(-1), reduction)
            e = ent.mean()
            return pg + vf_coef * vl - e_coef * e, (pg, vl, e)

        (loss, (pg, vl, e)), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
        updates, o_state = optimizer.update(grads, o_state, p)
        p = optax.apply_updates(p, updates)
        return p, o_state, (pg, vl, e)

    # rollout/last-obs staging is donated too (argnums 2/3): one dispatch
    # consumes the staged block exactly once (see ppo.py)
    train_phase_fn = train_phase  # raw callable: the Anakin path fuses it
    train_phase = fabric.compile(
        train_phase,
        name=f"{cfg.algo.name}.train_phase",
        donate_argnums=(0, 1, 2, 3),
        max_recompiles=cfg.algo.get("max_recompiles"),
    )
    guard_on = bool(cfg.buffer.get("transfer_guard", False))

    rollout_steps = int(cfg.algo.rollout_steps)
    sharded_envs, _ = fabric.env_sharding_plan(num_envs, "A2C")
    # buffer.share_data needs no branch here: this A2C takes ONE full-batch
    # gradient step over the global rollout, so the "shared global pool"
    # (share_data=True) and "per-rank batches + gradient all-reduce"
    # (share_data=False) semantics produce the same update by linearity
    # (reference: sheeprl/algos/a2c/a2c.py:41-54,371 minibatches instead)
    # GLOBAL env-step accounting: every process steps its own envs
    policy_steps_per_iter = num_envs * rollout_steps * fabric.num_processes
    if use_population:
        # every member steps its own env shard: the population multiplies
        # the env steps per fused update, so total_steps buys fewer updates
        policy_steps_per_iter *= pop_size
    total_iters = max(int(cfg.algo.total_steps) // policy_steps_per_iter, 1)
    if cfg.dry_run:
        total_iters = 1
    start_iter = int(state.get("update", 0)) + 1 if state else 1
    policy_step = int(state.get("policy_step", 0))
    last_log = int(state.get("last_log", 0))
    last_checkpoint = int(state.get("last_checkpoint", 0))
    base_lr = float(cfg.algo.optimizer.lr)

    # ---------------- Anakin fused rollout+train ----------------------------
    if use_anakin:
        from sheeprl_tpu.envs.jax.anakin import (
            init_actor_state,
            make_rollout_fn,
            traced_polynomial_decay,
        )

        def _sample(out, k):
            return sample_actions(out, actions_dim, is_continuous, k, dist_type=dist_type)

        rollout_fn = make_rollout_fn(
            venv,
            agent.apply,
            _sample,
            cnn_keys=cnn_keys,
            mlp_keys=mlp_keys,
            action_space=act_space,
            gamma=gamma,
            rollout_steps=rollout_steps,
            store_logprobs=False,  # A2C re-evaluates actions under current params
        )

        def anakin_phase(p, o_state, actor, k):
            """``lax.scan`` env rollout + GAE + the full-batch gradient step
            in ONE device program (lr annealing in-trace — see ppo.py)."""
            k_roll, k_next = jax.random.split(k)
            if cfg.algo.anneal_lr:
                o_state = set_learning_rate(
                    o_state,
                    traced_polynomial_decay(actor["update"], initial=base_lr, max_decay_steps=total_iters),
                )
            actor, rollout, last_obs, stats = rollout_fn(p, actor, k_roll)
            p, o_state, losses = train_phase_fn(p, o_state, rollout, last_obs)
            return p, o_state, actor, k_next, losses, stats

        if use_population:
            # ------------ population: vmap whole agents over P ------------
            from sheeprl_tpu import telemetry
            from sheeprl_tpu.population import (
                PBTConfig,
                PopulationMonitor,
                init_population_state,
                make_population_phase,
                tile_stack,
                write_population_summary,
            )

            pbt_cfg = PBTConfig.from_cfg(
                cfg, base={"lr": base_lr, "ent_coef": ent_coef}
            )

            def member_phase(p, o_state, actor, k, hp):
                """ONE member's fused rollout+train with its hyperparameters
                as traced data (A2C has no clip; lr rides the injected
                opt-state, ent_coef enters the loss)."""
                o_state = set_learning_rate(o_state, hp["lr"])
                actor, rollout, last_obs, stats = rollout_fn(p, actor, k)
                p, o_state, losses = train_phase_fn(p, o_state, rollout, last_obs, hp["ent_coef"])
                return p, o_state, actor, losses, stats

            population_step = fabric.compile(
                make_population_phase(member_phase, pbt_cfg),
                name=f"{cfg.algo.name}.population_phase",
                donate_argnums=(0, 1, 2, 3),
                max_recompiles=cfg.algo.get("max_recompiles"),
            )

            pop_resume = state.get("population") if state else None
            if state:
                params = fabric.replicate(jax.tree.map(jnp.asarray, state["agent"]))
                opt_state = fabric.replicate(state["opt_state"])
            else:
                params = jax.device_put(tile_stack(params, pop_size), fabric.replicated)
                opt_state = jax.device_put(jax.vmap(optimizer.init)(params), fabric.replicated)

            def _init_member(k):
                env_state, _ = venv.reset(k)
                return {
                    "env": env_state,
                    "ep_ret": jnp.zeros((num_envs,), jnp.float32),
                    "ep_len": jnp.zeros((num_envs,), jnp.int32),
                }

            members = jax.vmap(_init_member)(
                jax.random.split(jax.random.fold_in(key, fabric.global_rank + 1), pop_size)
            )
            members["update"] = jnp.full((pop_size,), start_iter - 1, jnp.int32)
            pop_state = init_population_state(members, pbt_cfg, num_envs)
            if pop_resume:
                pop_state["fitness"] = jnp.asarray(pop_resume["fitness"])
                pop_state["ep_count"] = jnp.asarray(pop_resume["ep_count"])
                pop_state["exploits"] = jnp.asarray(pop_resume["exploits"])
                hp_state = {name: jnp.asarray(v) for name, v in pop_resume["hp"].items()}
            else:
                hp_state = pbt_cfg.init_hyperparams(jax.random.fold_in(key, pop_size))
            pop_state = jax.device_put(pop_state, fabric.replicated)
            hp_state = jax.device_put(hp_state, fabric.replicated)
            pop_monitor = PopulationMonitor()
            telemetry.HUB.register("population", pop_monitor)
            anakin_step = None
            actor_state = None
        else:
            anakin_step = fabric.compile(
                anakin_phase,
                name=f"{cfg.algo.name}.anakin_phase",
                donate_argnums=(0, 1, 2),
                max_recompiles=cfg.algo.get("max_recompiles"),
            )
            actor_state = init_actor_state(
                fabric, venv, jax.random.fold_in(key, fabric.global_rank + 1), start_iter - 1, sharded_envs
            )
        rb = None
    else:
        rb = ReplayBuffer(
            rollout_steps,
            num_envs,
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None,
            obs_keys=obs_keys,
        )

    step_data: Dict[str, np.ndarray] = {}
    # rank-offset: each process's envs must be distinct streams or
    # multi-host DP collects the same data num_processes times
    if envs is not None:
        obs, _ = envs.reset(seed=cfg.seed + rank * num_envs)
    last_losses = None
    # per-rank player key stream, advanced inside policy_step_fn; the main
    # `key` stays rank-identical for train dispatches
    player_key = jax.device_put(
        # resume this rank's player RNG stream bit-exactly when saved
        jnp.asarray(state["player_key"]) if state and state.get("player_key") is not None
        else jax.random.fold_in(key, rank),
        host,
    )

    for update in range(start_iter, total_iters + 1):
        if use_anakin:
            # -------- fused rollout+train: ONE dispatch per update ---------
            with timer("Time/train_time"):
                with steady_guard(guard_on and update > start_iter):
                    if use_population:
                        # the WHOLE population trains in this one dispatch
                        params, opt_state, pop_state, hp_state, key, last_losses, ep_stats = (
                            population_step(params, opt_state, pop_state, hp_state, key)
                        )
                    else:
                        params, opt_state, actor_state, key, last_losses, ep_stats = anakin_step(
                            params, opt_state, actor_state, key
                        )
                if use_population:
                    # per-member (P,) losses → scalars for the aggregator
                    last_losses = jax.tree.map(lambda x: x.mean(), last_losses)
                policy_step += policy_steps_per_iter
            if cfg.metric.log_level > 0:
                from sheeprl_tpu.envs.jax.anakin import episode_stats_from_device

                rets, lens = episode_stats_from_device(ep_stats)
                for ep_ret, ep_len in zip(rets, lens):
                    aggregator.update("Rewards/rew_avg", float(ep_ret))
                    aggregator.update("Game/ep_len_avg", int(ep_len))
                if use_population:
                    # Population/* hub family: tiny D2H pulls on the logging
                    # cadence (the guard is H2D-scoped)
                    pop_monitor.observe(
                        pop_state["fitness"], hp_state, pop_state["exploits"]
                    )
        else:
            with timer("Time/env_interaction_time"):
                with jax.default_device(host):
                    for _ in range(rollout_steps):
                        policy_step += num_envs * fabric.num_processes
                        dev_obs = prepare_obs(obs, cnn_keys, mlp_keys)
                        actions, logprobs, _, player_key = policy_step_fn(
                            player_params, dev_obs, player_key
                        )
                        actions_np = np.asarray(actions)
                        next_obs, rewards, terminated, truncated, info = envs.step(
                            actions_for_env(actions_np, act_space)
                        )
                        dones = np.logical_or(terminated, truncated)
                        rewards = np.asarray(rewards, np.float32)
                        if np.any(truncated):
                            final_obs = final_obs_rows(info, np.nonzero(truncated)[0], obs_keys)
                            if final_obs is not None:
                                padded = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
                                for k in obs_keys:
                                    padded[k][truncated] = final_obs[k]
                                vals = np.asarray(
                                    values_fn(player_params, prepare_obs(padded, cnn_keys, mlp_keys))
                                )
                                rewards[truncated] += gamma * vals[truncated]

                        for k in obs_keys:
                            step_data[k] = np.asarray(obs[k])[None]
                        step_data["actions"] = actions_np[None]
                        step_data["rewards"] = rewards[None]
                        step_data["dones"] = dones[None].astype(np.float32)
                        rb.add({k: v[..., None] if v.ndim == 2 else v for k, v in step_data.items()})

                        obs = next_obs
                        for ep_ret, ep_len in episode_stats(info):
                            aggregator.update("Rewards/rew_avg", ep_ret)
                            aggregator.update("Game/ep_len_avg", ep_len)

            with timer("Time/train_time"):
                # donated device staging: host-numpy normalization + EXPLICIT
                # device_puts (data/device_replay.stage_rollout), rollout donated
                # into the one-dispatch update (see ppo.py)
                local = rb.buffer
                host_rollout = {k: obs_to_np(local[k], k in cnn_keys, rollout=True) for k in obs_keys}
                host_rollout["actions"] = np.asarray(local["actions"])
                host_rollout["rewards"] = np.asarray(local["rewards"][..., 0])
                host_rollout["dones"] = np.asarray(local["dones"][..., 0])
                rollout = stage_rollout(fabric, host_rollout, axis=1, sharded=sharded_envs)
                host_last = {k: obs_to_np(np.asarray(obs[k]), k in cnn_keys) for k in obs_keys}
                last_obs_dev = stage_rollout(fabric, host_last, axis=0, sharded=sharded_envs)
                with steady_guard(guard_on and update > start_iter):
                    params, opt_state, last_losses = train_phase(params, opt_state, rollout, last_obs_dev)
                player_params = fabric.to_host(params)

        # (Anakin mode anneals lr in-trace from the donated update counter)
        if cfg.algo.anneal_lr and not use_anakin:
            new_lr = polynomial_decay(update, initial=base_lr, final=0.0, max_decay_steps=total_iters)
            opt_state = set_learning_rate(opt_state, new_lr)

        if cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or update == total_iters or cfg.dry_run
        ):
            if last_losses is not None:
                pg, vl, e = last_losses
                aggregator.update("Loss/policy_loss", pg)
                aggregator.update("Loss/value_loss", vl)
                aggregator.update("Loss/entropy_loss", e)
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
            if use_population:
                # params/opt_state above are already the stacked (P, ...)
                # pytrees; the PBT carry rides its own subtree
                ckpt_state["population"] = {
                    "fitness": pop_state["fitness"],
                    "ep_count": pop_state["ep_count"],
                    "exploits": pop_state["exploits"],
                    "hp": hp_state,
                }
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_{rank}.ckpt"),
                state=ckpt_state,
            )
        if ckpt_mgr.preempted:
            fabric.print(f"Preemption: committed checkpoint at step {policy_step}, exiting")
            break

    if envs is not None:
        envs.close()
    ckpt_mgr.finalize()
    if use_population and fabric.is_global_zero:
        # machine-readable member snapshot for the run_ci PBT drill
        write_population_summary(log_dir, pop_state, hp_state, policy_step)
    if fabric.is_global_zero and cfg.algo.run_test and not ckpt_mgr.preempted:
        if use_population:
            # eval the current BEST member (fitness argmax)
            best = int(np.asarray(pop_state["fitness"]).argmax())
            player_params = fabric.to_host(jax.tree.map(lambda x: x[best], params))
        elif use_anakin:
            # the fused path never refreshes the host player copy — pull
            # the final params once for the eval episode
            player_params = fabric.to_host(params)
        test(agent, player_params, cfg, log_dir, logger)
    if logger is not None:
        logger.close()
