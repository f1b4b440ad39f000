"""PPO, decoupled (player/learner-overlapped) topology.

Capability parity with the reference's decoupled PPO
(reference: sheeprl/algos/ppo/ppo_decoupled.py:32-670): env interaction and
optimization proceed concurrently, with the player acting on slightly stale
policy weights while trainers optimize.

The reference implements this with N processes and three TorchCollective
groups (world scatter, player↔trainer-1 weight broadcast, trainer DDP
group).  The TPU-native equivalent needs NO process groups: JAX dispatch is
asynchronous, so the single controller

  1. dispatches the (donated, jitted) train phase for rollout *k* — the call
     returns immediately while the device crunches;
  2. collects rollout *k+1* on the host with the player params of rollout
     *k-1* (a one-iteration staleness, same semantics as the reference's
     player acting during trainer optimization);
  3. then syncs the refreshed params to the host player — by which time the
     device is done, so the transfer is the only wait.

Gradient all-reduce across the mesh happens inside the jitted step (GSPMD),
playing the role of the trainer DDP subgroup.  `fabric.devices` therefore
still scales training exactly like adding trainer ranks in the reference.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.ppo.agent import build_agent, evaluate_actions, sample_actions
from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.utils import (
    actions_for_env,
    normalize_obs_keys,
    obs_to_np,
    prepare_obs,
    spaces_to_dims,
    test,
)
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.envs.jax.anakin import read_obs_fn
from sheeprl_tpu.parallel.compile import compile_once
from sheeprl_tpu.utils.env import episode_stats, final_obs_rows, make_env, vectorize
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, flush_metrics
from sheeprl_tpu.utils.optim import build_optimizer, set_learning_rate
from sheeprl_tpu.utils.utils import normalize_tensor, polynomial_decay
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import gae, save_configs, should_unroll_updates, window_scan


def _build_train_fns(agent, optimizer, cfg, obs_keys, actions_dim, is_continuous, dist_type, obs_space):
    """The jitted policy/value/train-phase programs shared by the pipelined
    (single-controller) and dedicated (cross-process) decoupled topologies."""
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    # a fused actor's uint8 pixel leaves (sebulba's jax-native actors) back to
    # float frames; a rollout staged from the host passes through
    read_obs = read_obs_fn(cnn_keys, obs_space)
    reduction = cfg.algo.loss_reduction
    clip_vloss = bool(cfg.algo.clip_vloss)
    normalize_adv = bool(cfg.algo.normalize_advantages)
    vf_coef = float(cfg.algo.vf_coef)
    gamma = float(cfg.algo.gamma)
    gae_lambda = float(cfg.algo.gae_lambda)
    update_epochs = int(cfg.algo.update_epochs)

    def policy_step_fn(p, obs, k):
        # key advances INSIDE the jitted step (one host dispatch per env step)
        k_sample, k_next = jax.random.split(k)
        out, value = agent.apply(p, obs)
        actions, logprob, _ = sample_actions(out, actions_dim, is_continuous, k_sample, dist_type=dist_type)
        return actions, logprob, value[..., 0], k_next

    # compile-once routing (no fabric in scope for this shared builder:
    # use the module-level constructor directly)
    policy_step_fn = compile_once(
        policy_step_fn,
        name=f"{cfg.algo.name}.policy_step",
        max_recompiles=cfg.algo.get("max_recompiles"),
    )

    @jax.jit
    def values_fn(p, obs):
        _, value = agent.apply(p, obs)
        return value[..., 0]

    def loss_fn(p, batch, clip_coef, ent_coef):
        out, new_values = agent.apply(p, {k: batch[k] for k in obs_keys})
        new_logprobs, entropy = evaluate_actions(out, batch["actions"], actions_dim, is_continuous, dist_type=dist_type)
        adv = batch["advantages"]
        if normalize_adv:
            adv = normalize_tensor(adv)
        pg = policy_loss(new_logprobs, batch["logprobs"], adv, clip_coef, reduction)
        vl = value_loss(new_values[..., 0], batch["values"], batch["returns"], clip_coef, clip_vloss, reduction)
        ent = entropy_loss(entropy, reduction)
        return pg + vf_coef * vl + ent_coef * ent, (pg, vl, ent)

    def train_phase(p, o_state, rollout, last_obs, k, clip_coef, ent_coef, batch_size, num_minibatches):
        T, B = rollout["rewards"].shape
        flat_obs = {kk: rollout[kk].reshape((T * B,) + rollout[kk].shape[2:]) for kk in obs_keys}
        _, values = agent.apply(p, read_obs(flat_obs))
        values = values[..., 0].reshape(T, B)
        next_value = values_fn(p, last_obs)
        returns, advantages = gae(rollout["rewards"], values, rollout["dones"], next_value, gamma, gae_lambda)
        flat = dict(flat_obs)
        flat["actions"] = rollout["actions"].reshape(T * B, -1)
        flat["logprobs"] = rollout["logprobs"].reshape(T * B)
        flat["values"] = values.reshape(T * B)
        flat["returns"] = returns.reshape(T * B)
        flat["advantages"] = advantages.reshape(T * B)

        # XLA-CPU outlined-loop penalty is conv-specific: see
        # utils.window_scan / should_unroll_updates
        unroll_updates = should_unroll_updates(cnn_keys, update_epochs * num_minibatches)

        def epoch_body(carry, key_e):
            p, o_state = carry
            perm = jax.random.permutation(key_e, T * B)
            pad = num_minibatches * batch_size - (T * B)
            perm = jnp.concatenate([perm, perm[: max(pad, 0)]]) if pad > 0 else perm

            def mb_body(i, carry2):
                p, o_state, _ = carry2
                idx = jax.lax.dynamic_slice(perm, (i * batch_size,), (batch_size,))
                batch = read_obs({kk: jnp.take(vv, idx, axis=0) for kk, vv in flat.items()})
                (_, (pg, vl, ent)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    p, batch, clip_coef, ent_coef
                )
                updates, o_state = optimizer.update(grads, o_state, p)
                p = optax.apply_updates(p, updates)
                return p, o_state, (pg, vl, ent)

            carry2 = (p, o_state, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())))
            if unroll_updates:
                for i in range(num_minibatches):
                    carry2 = mb_body(i, carry2)
                p, o_state, losses = carry2
            else:
                p, o_state, losses = jax.lax.fori_loop(0, num_minibatches, mb_body, carry2)
            return (p, o_state), losses

        (p, o_state), losses = window_scan(
            epoch_body,
            (p, o_state),
            jax.random.split(k, update_epochs),
            unroll_limit=32,
            unroll=unroll_updates,
        )
        return p, o_state, jax.tree.map(lambda x: x[-1], losses)

    train_phase_raw = train_phase  # the sebulba learner fuses it (concat + GAE + epochs)
    train_phase = compile_once(
        train_phase,
        name=f"{cfg.algo.name}.train_phase",
        donate_argnums=(0, 1),
        static_argnames=("batch_size", "num_minibatches"),
        max_recompiles=cfg.algo.get("max_recompiles"),
    )

    return policy_step_fn, values_fn, train_phase, train_phase_raw


def _run_rollout(ctx, obs, p_params, key, fold_rank=None):
    """THE env-interaction rollout loop, shared by the pipelined and the
    dedicated decoupled topologies (one copy of the truncation-bootstrap /
    episode-stats / buffer-layout logic).  Returns
    ``(last_obs, numpy_rollout, key, policy_steps_taken)``; callers marshal
    the numpy stacks to their own device/mesh layout.  ``fold_rank`` keeps
    per-rank action sampling decorrelated where the base key stream must
    stay rank-identical (the pipelined multi-process path)."""
    envs, rb, aggregator = ctx["envs"], ctx["rb"], ctx["aggregator"]
    policy_step_fn, values_fn = ctx["policy_step_fn"], ctx["values_fn"]
    obs_keys, cnn_keys, mlp_keys = ctx["obs_keys"], ctx["cnn_keys"], ctx["mlp_keys"]
    act_space, gamma = ctx["act_space"], ctx["gamma"]
    steps = 0
    with jax.default_device(ctx["host"]):
        # one fold at entry starts a (rank-decorrelated) player stream that
        # then advances INSIDE policy_step_fn — one dispatch per env step;
        # the base `key` advances once per rollout, rank-identically
        sk = jax.random.fold_in(key, fold_rank if fold_rank is not None else 997)
        key, _ = jax.random.split(key)
        for _ in range(ctx["rollout_steps"]):
            steps += ctx["step_increment"]
            dev_obs = prepare_obs(obs, cnn_keys, mlp_keys)
            actions, logprobs, _, sk = policy_step_fn(p_params, dev_obs, sk)
            actions_np = np.asarray(actions)
            next_obs, rewards, terminated, truncated, info = envs.step(
                actions_for_env(actions_np, act_space)
            )
            dones = np.logical_or(terminated, truncated)
            rewards = np.asarray(rewards, np.float32)
            if np.any(truncated):
                # truncation bootstrap: add gamma*V(s_T) to rewards of
                # truncated envs (reference: sheeprl/algos/ppo/ppo.py:287-306)
                final_obs = final_obs_rows(info, np.nonzero(truncated)[0], obs_keys)
                if final_obs is not None:
                    padded = {kk: np.asarray(next_obs[kk]).copy() for kk in obs_keys}
                    for kk in obs_keys:
                        padded[kk][truncated] = final_obs[kk]
                    vals = np.asarray(values_fn(p_params, prepare_obs(padded, cnn_keys, mlp_keys)))
                    rewards[truncated] += gamma * vals[truncated]
            step_data = {}
            for kk in obs_keys:
                step_data[kk] = np.asarray(obs[kk])[None]
            step_data["actions"] = actions_np[None]
            step_data["logprobs"] = np.asarray(logprobs)[None]
            step_data["rewards"] = rewards[None]
            step_data["dones"] = dones[None].astype(np.float32)
            rb.add({kk: v[..., None] if v.ndim == 2 else v for kk, v in step_data.items()})
            obs = next_obs
            for ep_ret, ep_len in episode_stats(info):
                aggregator.update("Rewards/rew_avg", ep_ret)
                aggregator.update("Game/ep_len_avg", ep_len)
    local = rb.buffer
    rollout = {kk: np.asarray(local[kk]) for kk in obs_keys}
    rollout["actions"] = np.asarray(local["actions"])
    rollout["logprobs"] = np.asarray(local["logprobs"][..., 0])
    rollout["rewards"] = np.asarray(local["rewards"][..., 0])
    rollout["dones"] = np.asarray(local["dones"][..., 0])
    return obs, rollout, key, steps


@register_algorithm(decoupled=True, name="ppo_decoupled")
def main(fabric: Any, cfg: Any) -> None:
    if cfg.buffer.get("share_data", False):
        import warnings

        warnings.warn(
            "buffer.share_data=True is ignored by decoupled PPO: the player "
            "already collects ONE global rollout that every trainer minibatches "
            "(reference: sheeprl/algos/ppo/ppo_decoupled.py:639-643)"
        )
    from sheeprl_tpu.parallel.topology import resolve_topology

    topo_name = resolve_topology(cfg, fabric)
    if topo_name == "pod":
        # the cross-host actor/learner split (docs/distributed.md)
        from sheeprl_tpu.sebulba.pod import run_pod

        run_pod(fabric, cfg)
        return
    if topo_name == "sebulba":
        # the Sebulba actor/learner device split (docs/sebulba.md)
        from sheeprl_tpu.sebulba.ppo import run_sebulba

        run_sebulba(fabric, cfg)
        return
    dedicated = (cfg.algo.get("player", {}) or {}).get("dedicated", False)
    if dedicated and fabric.num_processes > 1:
        # DEPRECATION SHIM: the two-rank (dedicated player process) split is
        # superseded by the single-controller Sebulba device split, which
        # keeps the overlap without shipping rollouts over host collectives
        import warnings

        warnings.warn(
            "algo.player.dedicated=True (the two-rank player/trainer split) "
            "is deprecated: use the Sebulba device split instead "
            "(topology=sebulba topology.actor_devices=K, docs/sebulba.md). "
            "The cross-process path still runs for now.",
            DeprecationWarning,
        )
        return _dedicated_main(fabric, cfg)
    if dedicated:
        import warnings

        warnings.warn(
            "algo.player.dedicated=True needs >= 2 processes (jax.distributed); "
            "falling back to the single-controller pipelined topology "
            "(deprecated — prefer topology=sebulba, docs/sebulba.md)",
            UserWarning,
        )
    rank = fabric.global_rank
    key = fabric.seed_everything(cfg.seed)

    log_dir = get_log_dir(fabric, cfg.root_dir, cfg.run_name, base=cfg.get("log_dir", "logs/runs"))
    logger = get_logger(fabric, cfg, log_dir)
    ckpt_mgr = fabric.get_checkpoint_manager(cfg, log_dir)
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    num_envs = cfg.env.num_envs
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
    dist_type = cfg.get("distribution", {}).get("type", "auto")

    state: Dict[str, Any] = {}
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)
    if state and state.get("key") is not None:
        # resume the rollout/train RNG stream bit-exactly (this loop threads
        # one key through collect_rollout; per-rank separation is fold_in'd
        # inside the policy step)
        key = jnp.asarray(state["key"])
    agent, params = build_agent(fabric, actions_dim, is_continuous, cfg, obs_space, state.get("agent"))
    optimizer = build_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm)
    opt_state = fabric.replicate(state.get("opt_state") or optimizer.init(params))

    aggregator = MetricAggregator(cfg.metric.aggregator.metrics if cfg.metric.log_level > 0 else {})
    timer.configure(cfg.metric)

    # on-policy loops honor algo.player.device (placement only; the sync
    # cadence options are meaningless on-policy: rollouts must use the
    # current weights)
    host = fabric.player_device(cfg)
    gamma = float(cfg.algo.gamma)
    policy_step_fn, values_fn, train_phase, _ = _build_train_fns(
        agent, optimizer, cfg, obs_keys, actions_dim, is_continuous, dist_type, obs_space
    )

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
    clip_coef_v = float(cfg.algo.clip_coef)
    ent_coef_v = float(cfg.algo.ent_coef)

    rb = ReplayBuffer(rollout_steps, num_envs, memmap=False, obs_keys=obs_keys)

    rollout_ctx = {
        "envs": envs, "rb": rb, "aggregator": aggregator, "host": host,
        "policy_step_fn": policy_step_fn, "values_fn": values_fn,
        "obs_keys": obs_keys, "cnn_keys": cnn_keys, "mlp_keys": mlp_keys,
        "act_space": act_space, "gamma": gamma,
        "rollout_steps": rollout_steps,
        # GLOBAL env-step accounting: every process steps its own envs
        "step_increment": num_envs * fabric.num_processes,
    }

    def collect_rollout(obs, player_params, key):
        """One rollout with the (possibly stale) player params; per-rank
        sampling folds the rank into the player key only (the shared key
        stream must stay rank-identical for the train dispatch)."""
        nonlocal policy_step
        obs, rollout_np, key, steps = _run_rollout(rollout_ctx, obs, player_params, key, fold_rank=rank)
        policy_step += steps
        from sheeprl_tpu.algos.ppo.ppo import _obs_to_device

        rollout = {}
        for k in obs_keys:
            rollout[k] = _obs_to_device(rollout_np[k], k in cnn_keys)
        for k in ("actions", "logprobs", "rewards", "dones"):
            rollout[k] = jnp.asarray(rollout_np[k])
        return obs, rollout, key

    # the train phase is a GLOBAL program: its batch covers all ranks
    sharded_envs, B = fabric.env_sharding_plan(num_envs, "decoupled PPO")
    T = rollout_steps
    global_bs = min(int(cfg.algo.per_rank_batch_size) * fabric.world_size, T * B)
    num_minibatches = -(-T * B // global_bs)

    def ship(rollout, axis=1):
        if sharded_envs:
            return fabric.shard_batch(rollout, axis=axis)
        return fabric.replicate(rollout)

    # ---------------- pipelined main loop -----------------------------------
    # rank-offset: each process's envs must be distinct streams or
    # multi-host DP collects the same data num_processes times
    obs, _ = envs.reset(seed=cfg.seed + rank * num_envs)
    player_params = fabric.to_host(params)
    last_losses = None

    with timer("Time/env_interaction_time"):
        obs, rollout, key = collect_rollout(obs, player_params, key)

    for update in range(start_iter, total_iters + 1):
        # 1. dispatch training for rollout k (async — returns immediately)
        with timer("Time/train_time"):
            key, tk = jax.random.split(key)
            params, opt_state, last_losses = train_phase(
                params, opt_state, ship(rollout),
                ship(prepare_obs(obs, cnn_keys, mlp_keys), axis=0),
                tk, jnp.float32(clip_coef_v), jnp.float32(ent_coef_v),
                batch_size=global_bs, num_minibatches=num_minibatches,
            )
        # 2. collect rollout k+1 with the stale player while the device trains
        if update < total_iters:
            with timer("Time/env_interaction_time"):
                obs, rollout, key = collect_rollout(obs, player_params, key)
        # 3. refresh the player (device is done by now; transfer is the wait)
        player_params = fabric.to_host(params)

        # schedules (reference: ppo_decoupled.py:586-594)
        if cfg.algo.anneal_lr:
            opt_state = set_learning_rate(
                opt_state,
                polynomial_decay(update, initial=float(cfg.algo.optimizer.lr), final=0.0, max_decay_steps=total_iters),
            )
        if cfg.algo.anneal_clip_coef:
            clip_coef_v = polynomial_decay(
                update, initial=float(cfg.algo.clip_coef), final=0.0, max_decay_steps=total_iters
            )
        if cfg.algo.anneal_ent_coef:
            ent_coef_v = polynomial_decay(
                update, initial=float(cfg.algo.ent_coef), final=0.0, max_decay_steps=total_iters
            )

        if cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or update == total_iters or cfg.dry_run
        ):
            if last_losses is not None:
                pg, vl, ent = last_losses
                aggregator.update("Loss/policy_loss", pg)
                aggregator.update("Loss/value_loss", vl)
                aggregator.update("Loss/entropy_loss", ent)
            last_log = flush_metrics(aggregator, timer, logger, policy_step, last_log)

        if ckpt_mgr.should_save(policy_step, last_checkpoint, final=update == total_iters):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": params,
                "opt_state": opt_state,
                "key": key,
                "update": update,
                "policy_step": policy_step,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            fabric.call(
                "on_checkpoint_player",
                ckpt_path=os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_{rank}.ckpt"),
                state=ckpt_state,
            )
        if ckpt_mgr.preempted:
            fabric.print(f"Preemption: committed checkpoint at step {policy_step}, exiting")
            break

    envs.close()
    ckpt_mgr.finalize()
    if fabric.is_global_zero and cfg.algo.run_test and not ckpt_mgr.preempted:
        test(agent, player_params, cfg, log_dir, logger)
    if logger is not None:
        logger.close()


def _dedicated_main(fabric: Any, cfg: Any) -> None:
    """Cross-process player/trainer split (``algo.player.dedicated=True``,
    requires >= 2 processes).

    Process topology, matching the reference's decoupled PPO
    (reference: sheeprl/algos/ppo/ppo_decoupled.py:32-365 player,
    :368-620 trainer, :623-670 group setup):

    * process 0 — the PLAYER: owns the envs, acts with a host-device policy
      copy, never joins the train mesh;
    * processes 1..N-1 — TRAINERS: own a sub-mesh over their devices (the
      reference's trainer-only DDP ``optimization_pg``) and run the jitted
      train phase, gradients all-reduced by GSPMD over the sub-mesh.

    Per-iteration protocol (reference's scatter/broadcast collectives →
    host object collectives over DCN):

    1. player broadcasts rollout *k* (+ final obs) to everyone  [src=0];
    2. trainers dispatch the train phase on rollout *k* while the player
       collects rollout *k+1* on weights from iteration *k-1* — the
       cross-process overlap the reference gets from its process split;
    3. the first trainer broadcasts refreshed weights (+losses, + full
       train state on checkpoint cadence) [src=1]; the player refreshes
       its policy and logs/saves.
    """
    rank = fabric.global_rank
    is_player = rank == 0
    key = fabric.seed_everything(cfg.seed)
    if is_player:
        # fork the player's key stream off the trainers' (the coupled path's
        # fold_in(rank) separation): without this, the player's action keys
        # at step i would exactly equal the trainers' train-phase keys
        key = jax.random.fold_in(key, 0x9E37)

    log_dir = get_log_dir(fabric, cfg.root_dir, cfg.run_name, base=cfg.get("log_dir", "logs/runs"))
    logger = get_logger(fabric, cfg, log_dir)
    # commit-protocol/async saves via the manager; cadence stays the
    # deterministic ckpt_due below, and preemption is NOT polled here — the
    # lockstep player↔trainer message protocol cannot tolerate one rank
    # unilaterally breaking out (a SIGTERM usually reaches only one process)
    ckpt_mgr = fabric.get_checkpoint_manager(cfg, log_dir)
    if is_player:
        save_configs(cfg, log_dir)

    num_envs = cfg.env.num_envs
    envs = None
    if is_player:
        envs = vectorize(
            cfg,
            [
                make_env(cfg, cfg.seed + i, 0, run_name=log_dir, vector_env_idx=i)
                for i in range(num_envs)
            ],
        )
        spaces = (envs.single_observation_space, envs.single_action_space)
    else:
        spaces = None
    # trainers never build envs; they learn the spaces from the player
    obs_space, act_space = fabric.broadcast_object(spaces, src=0)
    normalize_obs_keys(cfg, obs_space)
    actions_dim, is_continuous = spaces_to_dims(act_space)
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys
    dist_type = cfg.get("distribution", {}).get("type", "auto")
    gamma = float(cfg.algo.gamma)

    state: Dict[str, Any] = {}
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)

    from sheeprl_tpu.parallel.fabric import get_trainer_fabric

    optimizer = build_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm)
    # honor algo.player.device (host by default; 'accelerator' = the player
    # process's own otherwise-idle chip, for big pixel encoders)
    host = fabric.player_device(cfg)
    if is_player:
        # player-only agent: params live on the player device, no mesh involved
        from sheeprl_tpu.parallel.fabric import get_single_device_fabric

        player_fabric = get_single_device_fabric(fabric, device=host)
        agent, params = build_agent(
            player_fabric, actions_dim, is_continuous, cfg, obs_space, state.get("agent")
        )
        player_params = fabric.copy_to(params, host)
        trainer_fabric = None
    else:
        trainer_fabric = get_trainer_fabric(fabric, player_process=0)
        agent, params = build_agent(
            trainer_fabric, actions_dim, is_continuous, cfg, obs_space, state.get("agent")
        )
        opt_state = trainer_fabric.replicate(state.get("opt_state") or optimizer.init(params))

    policy_step_fn, values_fn, train_phase, _ = _build_train_fns(
        agent, optimizer, cfg, obs_keys, actions_dim, is_continuous, dist_type, obs_space
    )

    aggregator = MetricAggregator(cfg.metric.aggregator.metrics if cfg.metric.log_level > 0 else {})
    timer.configure(cfg.metric)

    rollout_steps = int(cfg.algo.rollout_steps)
    policy_steps_per_iter = num_envs * rollout_steps  # only the player steps envs
    total_iters = max(int(cfg.algo.total_steps) // policy_steps_per_iter, 1)
    if cfg.dry_run:
        total_iters = 1
    start_iter = int(state.get("update", 0)) + 1 if state else 1
    policy_step = int(state.get("policy_step", 0))
    last_log = int(state.get("last_log", 0))
    last_checkpoint = int(state.get("last_checkpoint", 0))
    clip_coef_v = float(cfg.algo.clip_coef)
    ent_coef_v = float(cfg.algo.ent_coef)

    # deterministic on every process: both sides agree when a checkpoint is
    # due without an extra message.  The player's own policy_step counter
    # runs one rollout AHEAD of the trainers' (it collects k+1 before sync
    # B of iteration k), so cadence uses the canonical per-iteration step.
    base_step = policy_step

    def canonical_step(update: int) -> int:
        return base_step + (update - start_iter + 1) * policy_steps_per_iter

    def ckpt_due(step: int, update: int) -> bool:
        return (
            cfg.checkpoint.every > 0 and step - last_checkpoint >= cfg.checkpoint.every
        ) or (update == total_iters and cfg.checkpoint.save_last)

    # ---------------- player-side rollout ------------------------------------
    rb = ReplayBuffer(rollout_steps, num_envs, memmap=False, obs_keys=obs_keys) if is_player else None

    if is_player:
        rollout_ctx = {
            "envs": envs, "rb": rb, "aggregator": aggregator, "host": host,
            "policy_step_fn": policy_step_fn, "values_fn": values_fn,
            "obs_keys": obs_keys, "cnn_keys": cnn_keys, "mlp_keys": mlp_keys,
            "act_space": act_space, "gamma": gamma,
            "rollout_steps": rollout_steps,
            "step_increment": num_envs,  # only the player steps envs
        }

    def collect_rollout(obs, p_params, k):
        """One rollout; returns raw numpy stacks (shipped over DCN).  The
        player's key stream is already forked off the trainers' (fold_in at
        seed time), so no per-step rank folding is needed."""
        nonlocal policy_step
        obs, rollout_np, k, steps = _run_rollout(rollout_ctx, obs, p_params, k)
        policy_step += steps
        return obs, rollout_np, k

    # ---------------- trainer-side batch assembly ----------------------------
    if not is_player:
        from sheeprl_tpu.parallel.fabric import host_tree_to_mesh

        tmesh = trainer_fabric.mesh
        t_world = trainer_fabric.world_size
        shard_envs = num_envs % t_world == 0
        global_bs = min(int(cfg.algo.per_rank_batch_size) * t_world, rollout_steps * num_envs)
        num_minibatches = -(-rollout_steps * num_envs // global_bs)

        def to_mesh(tree, axis=1):
            return host_tree_to_mesh(tree, tmesh, axis=axis, shard=shard_envs)

        def device_rollout(rollout_np):
            # numpy-side normalize/layout (NO accelerator round-trip: the
            # mesh landing below is the single upload)
            out = {}
            for kk in obs_keys:
                out[kk] = obs_to_np(rollout_np[kk], kk in cnn_keys, rollout=True)
            for kk in ("actions", "logprobs", "rewards", "dones"):
                out[kk] = np.asarray(rollout_np[kk], np.float32)
            return to_mesh(out, axis=1)

    # ---------------- lockstep protocol --------------------------------------
    acc_train_times: Dict[str, float] = {}
    obs = None
    if is_player:
        obs, _ = envs.reset(seed=cfg.seed)
        with timer("Time/env_interaction_time"):
            obs, rollout_np, key = collect_rollout(obs, player_params, key)
    else:
        rollout_np = None

    for update in range(start_iter, total_iters + 1):
        if is_player:
            payload = (rollout_np, {kk: np.asarray(obs[kk]) for kk in obs_keys})
        else:
            payload = None
        rollout_np, last_obs_np = fabric.broadcast_object(payload, src=0)  # sync A
        if not is_player:
            policy_step += policy_steps_per_iter
            with timer("Time/train_time"):
                key, tk = jax.random.split(key)
                params, opt_state, losses = train_phase(
                    params, opt_state, device_rollout(rollout_np),
                    to_mesh({kk: obs_to_np(last_obs_np[kk], kk in cnn_keys) for kk in obs_keys}, axis=0),
                    tk, jnp.float32(clip_coef_v), jnp.float32(ent_coef_v),
                    batch_size=global_bs, num_minibatches=num_minibatches,
                )
        elif update < total_iters:
            # overlap: the player collects rollout k+1 (stale weights) while
            # the trainers crunch rollout k
            with timer("Time/env_interaction_time"):
                obs, rollout_np, key = collect_rollout(obs, player_params, key)

        # sync B: refreshed weights (+ state on checkpoint cadence) → player
        due = ckpt_due(canonical_step(update), update)
        if rank == 1:
            from sheeprl_tpu.parallel.fabric import fetch_local

            host_params = fetch_local(params)
            host_losses = tuple(float(x) for x in fetch_local(losses))
            extra = fetch_local(opt_state) if due else None
            back = (host_params, host_losses, extra, timer.to_dict(reset=True))
        else:
            back = None
        host_params, host_losses, opt_for_ckpt, train_times = fabric.broadcast_object(back, src=1)
        for tk_, tv_ in (train_times or {}).items():
            acc_train_times[tk_] = acc_train_times.get(tk_, 0.0) + tv_

        # schedules march in lockstep on every process
        if cfg.algo.anneal_lr and not is_player:
            opt_state = set_learning_rate(
                opt_state,
                polynomial_decay(update, initial=float(cfg.algo.optimizer.lr), final=0.0, max_decay_steps=total_iters),
            )
        if cfg.algo.anneal_clip_coef:
            clip_coef_v = polynomial_decay(update, initial=float(cfg.algo.clip_coef), final=0.0, max_decay_steps=total_iters)
        if cfg.algo.anneal_ent_coef:
            ent_coef_v = polynomial_decay(update, initial=float(cfg.algo.ent_coef), final=0.0, max_decay_steps=total_iters)

        if is_player:
            player_params = jax.device_put(host_params, host)
            if cfg.metric.log_level > 0 and (
                policy_step - last_log >= cfg.metric.log_every or update == total_iters or cfg.dry_run
            ):
                pg, vl, ent = host_losses
                aggregator.update("Loss/policy_loss", pg)
                aggregator.update("Loss/value_loss", vl)
                aggregator.update("Loss/entropy_loss", ent)
                last_log = flush_metrics(
                    aggregator, timer, logger, policy_step, last_log,
                    extra_times=dict(acc_train_times),
                )
                acc_train_times.clear()
        if due:
            # every process calls the hook: fabric.save writes on the player
            # (global zero) and barriers everyone; keep_last pruning applies
            last_checkpoint = canonical_step(update)
            fabric.call(
                "on_checkpoint_player",
                ckpt_path=os.path.join(log_dir, "checkpoint", f"ckpt_{last_checkpoint}_0.ckpt"),
                state={
                    "agent": host_params,
                    "opt_state": opt_for_ckpt,
                    "update": update,
                    "policy_step": last_checkpoint,
                    "last_log": last_log,
                    "last_checkpoint": last_checkpoint,
                },
            )

    ckpt_mgr.finalize()
    if is_player:
        envs.close()
        if cfg.algo.run_test:
            test(agent, player_params, cfg, log_dir, logger)
    if logger is not None:
        logger.close()
