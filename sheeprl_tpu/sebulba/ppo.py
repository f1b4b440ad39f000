"""Sebulba PPO: the decoupled PPO loop rebuilt on the actor–learner device
split (``topology=sebulba``; docs/sebulba.md).

Dataflow, per :mod:`sheeprl_tpu.parallel.topology`:

* **cpu-gym actors** — ``topology.env_workers`` driver threads step env
  slices (subprocess workers under ``env.sync_env=False``) and round-trip
  observation blocks through the actor devices' batched AOT inference
  engines; each worker assembles ``(T, B_w)`` segments and pushes them
  into the device-resident trajectory queue.
* **jax-env actors** (``env=jax_*``) — each actor device runs an
  Anakin-style fused rollout shard (env scan + policy + truncation
  bootstrap in ONE executable over a donated carry); segments move
  device-to-device into the queue.
* **learner** — pops one segment per producer, and its compiled
  ``learner_phase`` concatenates them along the env axis, recomputes
  values, runs GAE + all epochs/minibatches (the exact
  ``ppo_decoupled`` train program), then broadcasts fresh params
  learner→actors with the :class:`~sheeprl_tpu.parallel.topology.
  ParamBroadcast` staleness gate.

The learner runs on the calling thread; actors and workers are threads
(JAX dispatch is thread-safe, and XLA execution releases the GIL, so
actor inference genuinely overlaps learner optimization even before the
device split makes them independent).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.ppo.agent import build_agent, sample_actions
from sheeprl_tpu.algos.ppo.ppo_decoupled import _build_train_fns
from sheeprl_tpu.algos.ppo.utils import (
    actions_for_env,
    normalize_obs_keys,
    obs_to_np,
    spaces_to_dims,
    test,
)
from sheeprl_tpu.parallel.topology import DeviceTopology, ParamBroadcast, topology_cfg
from sheeprl_tpu.sebulba.actor import ActorEngine, EnvWorker, FusedActor, WorkerSupervisor, derive_ladder
from sheeprl_tpu.sebulba.queues import ObsQueue, TrajQueue
from sheeprl_tpu.sebulba.runner import (
    StatsSink,
    arm_preemption,
    build_worker_fleet,
    clamp_queue_slots,
    collect_run_stats,
    drain_preemptible,
    shutdown,
)
from sheeprl_tpu.utils.env import episode_stats, final_obs_rows, make_env, vectorize
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, flush_metrics
from sheeprl_tpu.utils.optim import build_optimizer, set_learning_rate
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs


class PPOWorkerProtocol:
    """Per-step semantics of a PPO env worker: prepared-observation blocks
    out, sampled actions back, truncation bootstrap via a SECOND inference
    request on the (padded) final-obs block — same shape, same executable,
    no ladder churn."""

    def __init__(self, obs_keys, cnn_keys, mlp_keys, act_space, gamma):
        self.obs_keys = tuple(obs_keys)
        self.cnn_keys = tuple(cnn_keys)
        self.mlp_keys = tuple(mlp_keys)
        self.act_space = act_space
        self.gamma = float(gamma)

    def prepare(self, obs: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        out = {}
        for k in self.cnn_keys:
            out[k] = obs_to_np(obs[k], is_image=True)
        for k in self.mlp_keys:
            out[k] = obs_to_np(obs[k], is_image=False)
        return out

    def on_reset(self, worker: EnvWorker, obs: Dict[str, np.ndarray]) -> None:
        pass

    def run_segment(
        self, worker: EnvWorker, envs: Any, obs: Dict[str, np.ndarray], steps: int
    ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray], List[Tuple[float, int]], int]:
        num_envs = envs.num_envs
        rows: Dict[str, List[np.ndarray]] = {k: [] for k in self.obs_keys}
        for k in ("actions", "logprobs", "rewards", "dones"):
            rows[k] = []
        ep_stats: List[Tuple[float, int]] = []
        for _ in range(steps):
            worker.beat()
            block = self.prepare(obs)
            out = worker.infer(block)
            actions = np.asarray(out["actions"])
            next_obs, rewards, terminated, truncated, info = envs.step(
                actions_for_env(actions, self.act_space)
            )
            rewards = np.asarray(rewards, np.float32)
            dones = np.logical_or(terminated, truncated)
            if np.any(truncated):
                # truncation bootstrap r += γ·V(final_obs): the final-obs
                # batch is padded to the full block so the actor serves it
                # from the SAME ladder rung (reference: ppo.py:287-306)
                final_obs = final_obs_rows(info, np.nonzero(truncated)[0], self.obs_keys)
                if final_obs is not None:
                    padded = {k: np.asarray(next_obs[k]).copy() for k in self.obs_keys}
                    for k in self.obs_keys:
                        padded[k][truncated] = final_obs[k]
                    vout = worker.infer(self.prepare(padded))
                    vals = np.asarray(vout["values"])
                    rewards[truncated] += self.gamma * vals[truncated]
            for k in self.obs_keys:
                rows[k].append(block[k])
            rows["actions"].append(actions.reshape(num_envs, -1))
            rows["logprobs"].append(np.asarray(out["logprobs"]).reshape(num_envs))
            rows["rewards"].append(rewards.reshape(num_envs))
            rows["dones"].append(dones.astype(np.float32).reshape(num_envs))
            obs = next_obs
            ep_stats.extend(episode_stats(info))
        segment = {k: np.stack(v, axis=0) for k, v in rows.items()}
        last = self.prepare(obs)
        for k in self.obs_keys:
            segment[f"last_{k}"] = last[k]
        return obs, segment, ep_stats, steps * num_envs


def run_sebulba(fabric: Any, cfg: Any) -> Dict[str, Any]:
    """Train decoupled PPO through the Sebulba topology.  Returns the run's
    counters (``runner.collect_run_stats``)."""
    if fabric.num_processes > 1:
        # multi-process runs split actors and learner across HOSTS, not
        # devices: the in-process topology below assumes one device view
        from sheeprl_tpu.sebulba.pod import run_pod

        run_pod(fabric, cfg)
        return {}
    from sheeprl_tpu.envs.jax.registry import is_jax_native

    topo_cfg = topology_cfg(cfg)
    topo = DeviceTopology.from_config(fabric, cfg)
    learner_fab = topo.learner_fabric
    fabric.print(topo.describe())
    key = fabric.seed_everything(cfg.seed)

    log_dir = get_log_dir(fabric, cfg.root_dir, cfg.run_name, base=cfg.get("log_dir", "logs/runs"))
    logger = get_logger(fabric, cfg, log_dir)
    ckpt_mgr = fabric.get_checkpoint_manager(cfg, log_dir)
    save_configs(cfg, log_dir)

    num_envs = int(cfg.env.num_envs)
    rollout_steps = int(cfg.algo.rollout_steps)
    jax_native = is_jax_native(cfg)
    num_actors = topo.num_actors

    # ---------------- spaces -------------------------------------------------
    if jax_native:
        from sheeprl_tpu.envs.jax.core import VectorJaxEnv
        from sheeprl_tpu.envs.jax.registry import jax_env_from_cfg

        if num_envs % num_actors:
            raise ValueError(
                f"sebulba jax actors need env.num_envs ({num_envs}) divisible "
                f"by topology.actor_devices ({num_actors})"
            )
        envs_per_actor = num_envs // num_actors
        venvs = [VectorJaxEnv(jax_env_from_cfg(cfg), envs_per_actor) for _ in range(num_actors)]
        obs_space = venvs[0].single_observation_space
        act_space = venvs[0].single_action_space
        num_workers = num_actors
    else:
        num_workers = max(1, int(topo_cfg.get("env_workers", 2)))
        if num_envs % num_workers:
            raise ValueError(
                f"sebulba env workers need env.num_envs ({num_envs}) divisible "
                f"by topology.env_workers ({num_workers})"
            )
        probe = make_env(cfg, cfg.seed, 0, run_name=log_dir, vector_env_idx=0)()
        obs_space, act_space = probe.observation_space, probe.action_space
        probe.close()
    normalize_obs_keys(cfg, obs_space)
    actions_dim, is_continuous = spaces_to_dims(act_space)
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys
    dist_type = cfg.get("distribution", {}).get("type", "auto")
    gamma = float(cfg.algo.gamma)

    # ---------------- learner: agent + train program -------------------------
    state: Dict[str, Any] = {}
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)
    if state and state.get("key") is not None:
        key = jnp.asarray(state["key"])
    agent, params = build_agent(learner_fab, actions_dim, is_continuous, cfg, obs_space, state.get("agent"))
    optimizer = build_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm)
    opt_state = learner_fab.replicate(state.get("opt_state") or optimizer.init(params))

    _, _, _, train_phase_raw = _build_train_fns(
        agent, optimizer, cfg, obs_keys, actions_dim, is_continuous, dist_type, obs_space
    )

    T, B = rollout_steps, num_envs
    global_bs = min(int(cfg.algo.per_rank_batch_size) * learner_fab.world_size, T * B)
    num_minibatches = -(-T * B // global_bs)
    n_producers = num_workers

    def learner_phase(p, o_state, segs, k, clip_coef, ent_coef):
        """Concat the producers' segments along the env axis + the full
        decoupled PPO train program, in ONE learner-mesh executable."""
        rollout = {
            kk: jnp.concatenate([s[kk] for s in segs], axis=1)
            for kk in obs_keys + ("actions", "logprobs", "rewards", "dones")
        }
        last_obs = {
            kk: jnp.concatenate([s[f"last_{kk}"] for s in segs], axis=0) for kk in obs_keys
        }
        return train_phase_raw(
            p, o_state, rollout, last_obs, k, clip_coef, ent_coef,
            batch_size=global_bs, num_minibatches=num_minibatches,
        )

    # donate params/opt only: the concat re-lays the segment buffers out, so
    # XLA cannot reuse them anyway (donating them just prints the
    # "donated buffers were not usable" warning)
    learner_phase = learner_fab.compile(
        learner_phase,
        name=f"{cfg.algo.name}.sebulba_learner_phase",
        donate_argnums=(0, 1),
        max_recompiles=cfg.algo.get("max_recompiles"),
    )

    # ---------------- broadcast + queues -------------------------------------
    broadcast = ParamBroadcast(
        fabric,
        topo.actor_devices,
        max_staleness=int(topo_cfg.get("max_staleness", 2)),
        gate_timeout_s=float(topo_cfg.get("queue_timeout_s", 300.0)),
    )
    sync_every = max(1, int(topo_cfg.get("sync_every", 1)))

    traj_queue = TrajQueue(
        clamp_queue_slots(topo_cfg, n_producers),
        rollout_steps,
        learner_fab,
        stage=True,
        bootstrap_keys=tuple(f"last_{k}" for k in obs_keys),
        timeout_s=float(topo_cfg.get("queue_timeout_s", 300.0)),
    )
    stats_sink = StatsSink()
    stop_event = threading.Event()
    guard_on = bool(cfg.buffer.get("transfer_guard", False))

    # ---------------- actors -------------------------------------------------
    engines: List[Any] = []
    supervisor: Optional[WorkerSupervisor] = None
    obs_queue: Optional[ObsQueue] = None

    if jax_native:
        from sheeprl_tpu.envs.jax.anakin import make_rollout_fn
        from sheeprl_tpu.parallel.compile import compile_once

        def _sample(out, k):
            return sample_actions(out, actions_dim, is_continuous, k, dist_type=dist_type)

        for i, (dev, venv) in enumerate(zip(topo.actor_devices, venvs)):
            rollout_fn = make_rollout_fn(
                venv, agent.apply, _sample,
                cnn_keys=cnn_keys, mlp_keys=mlp_keys, action_space=act_space,
                gamma=gamma, rollout_steps=rollout_steps,
            )

            def actor_rollout(p, actor, k, _roll=rollout_fn):
                k_roll, k_next = jax.random.split(k)
                actor, traj, last_obs, stats = _roll(p, actor, k_roll)
                return actor, traj, last_obs, stats, k_next

            exe = compile_once(
                actor_rollout,
                name=f"sebulba.fused_rollout[{i}]",
                donate_argnums=(1, 2),
                max_recompiles=cfg.algo.get("max_recompiles"),
            )
            env_state, _ = venv.reset(jax.random.fold_in(key, 0xAC + i))
            carry = jax.device_put(
                {
                    "env": env_state,
                    "ep_ret": jnp.zeros((venv.num_envs,), jnp.float32),
                    "ep_len": jnp.zeros((venv.num_envs,), jnp.int32),
                    "update": jnp.asarray(0, jnp.int32),
                },
                dev,
            )
            engines.append(
                FusedActor(
                    i, dev, exe, carry, jax.random.fold_in(key, 0xF0 + i), broadcast,
                    traj_queue,
                    stop_event=stop_event,
                    stats_sink=stats_sink,
                    env_steps_per_segment=rollout_steps * venv.num_envs,
                    guard=guard_on,
                )
            )
    else:
        envs_per_worker = num_envs // num_workers
        protocol = PPOWorkerProtocol(obs_keys, cnn_keys, mlp_keys, act_space, gamma)
        obs_queue = ObsQueue(max_pending=2 * num_workers)
        ladder = derive_ladder(
            envs_per_worker, num_workers, topo_cfg.get("actor_batch_ladder")
        )

        def policy_fn(p, obs, k):
            k_sample, k_next = jax.random.split(k)
            out, value = agent.apply(p, obs)
            actions, logprob, _ = sample_actions(
                out, actions_dim, is_continuous, k_sample, dist_type=dist_type
            )
            return {"actions": actions, "logprobs": logprob, "values": value[..., 0]}, k_next

        # prepared-obs leaf spec (post obs_to_np layout) from a probe reset
        probe_prep = protocol.prepare(
            {k: np.zeros((1,) + tuple(obs_space[k].shape), obs_space[k].dtype) for k in obs_keys}
        )
        obs_spec = {k: (tuple(v.shape[1:]), v.dtype) for k, v in probe_prep.items()}
        param_spec = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        for i, dev in enumerate(topo.actor_devices):
            eng = ActorEngine(
                i, dev, policy_fn, obs_spec, param_spec, ladder, envs_per_worker,
                obs_queue, broadcast, jax.random.fold_in(key, 0xF0 + i),
                max_wait_s=float(topo_cfg.get("max_wait_ms", 20.0)) / 1e3,
                max_recompiles=cfg.algo.get("max_recompiles"),
            )
            if cfg.algo.get("compile_warmup", True):
                eng.warmup(fabric.compile_pool, join=False)
            engines.append(eng)
        fabric.compile_pool.join()

        supervisor = build_worker_fleet(
            cfg, topo_cfg,
            protocol=protocol, obs_queue=obs_queue, traj_queue=traj_queue,
            segment_steps=rollout_steps, num_workers=num_workers,
            envs_per_worker=envs_per_worker, log_dir=log_dir,
            stop_event=stop_event, stats_sink=stats_sink,
        )

    # ---------------- counters -----------------------------------------------
    aggregator = MetricAggregator(cfg.metric.aggregator.metrics if cfg.metric.log_level > 0 else {})
    timer.configure(cfg.metric)
    policy_steps_per_iter = num_envs * rollout_steps
    total_iters = max(int(cfg.algo.total_steps) // policy_steps_per_iter, 1)
    if cfg.dry_run:
        total_iters = 1
    start_iter = int(state.get("update", 0)) + 1 if state else 1
    policy_step = int(state.get("policy_step", 0))
    last_log = int(state.get("last_log", 0))
    last_checkpoint = int(state.get("last_checkpoint", 0))
    clip_coef_v = float(cfg.algo.clip_coef)
    ent_coef_v = float(cfg.algo.ent_coef)
    base_lr = float(cfg.algo.optimizer.lr)

    staleness_sum = 0
    staleness_max = 0
    segments_consumed = 0
    env_steps_consumed = 0
    updates_done = 0
    last_losses = None

    # ---------------- run ----------------------------------------------------
    # queue/broadcast counters become live hub sources for the duration of
    # the run (scrapeable via /metrics mid-run, not just at log intervals);
    # a fresh span window makes the first flush's phase breakdown cover the
    # training loop, not agent construction/compilation
    from sheeprl_tpu.telemetry import HUB, SPANS

    HUB.register("sebulba.traj_queue", traj_queue.metrics)
    HUB.register("sebulba.broadcast", broadcast.metrics)
    SPANS.roll_window()

    arm_preemption(cfg)

    def save_checkpoint() -> None:
        # closure over the live loop variables: the cadence save and the
        # preemption final save must write the identical state
        fabric.call(
            "on_checkpoint_player",
            ckpt_path=os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_0.ckpt"),
            state={
                "agent": params,
                "opt_state": opt_state,
                "key": key,
                "update": update,
                "policy_step": policy_step,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            },
        )

    try:
        # inside the try: the first publish crosses fabric.copy_to (a
        # fault-injection site) — a throw here must still unregister
        broadcast.publish(params, version=start_iter - 1)
        for eng in engines:
            eng.start()
        if supervisor is not None:
            supervisor.start()
        update = start_iter - 1
        for update in range(start_iter, total_iters + 1):
            with timer("Time/env_interaction_time"):
                items = drain_preemptible(
                    traj_queue, n_producers, engines, supervisor,
                    ckpt_mgr=ckpt_mgr, fabric=fabric, policy_step=policy_step,
                    save_checkpoint=save_checkpoint,
                )
            if items is None:  # preempted mid-wait: committed save done
                break
            segs = tuple(item[0] for item in items)
            for _, meta in items:
                lag = broadcast.version - int(meta.get("version", 0))
                staleness_sum += lag
                staleness_max = max(staleness_max, lag)
                env_steps_consumed += int(meta.get("env_steps", 0))
            segments_consumed += len(items)
            policy_step += policy_steps_per_iter
            updates_done += 1

            with timer("Time/train_time"):
                key, tk = jax.random.split(key)
                params, opt_state, last_losses = learner_phase(
                    params, opt_state, segs, tk,
                    jnp.float32(clip_coef_v), jnp.float32(ent_coef_v),
                )
            if update % sync_every == 0 or update == total_iters:
                broadcast.publish(params, version=update)
                broadcast.gate()
            if supervisor is not None:
                supervisor.check()

            # schedules (host-side, like the pipelined decoupled loop)
            if cfg.algo.anneal_lr:
                opt_state = set_learning_rate(
                    opt_state,
                    polynomial_decay(update, initial=base_lr, final=0.0, max_decay_steps=total_iters),
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
                for ep_ret, ep_len in stats_sink.drain():
                    aggregator.update("Rewards/rew_avg", float(ep_ret))
                    aggregator.update("Game/ep_len_avg", int(ep_len))
                if last_losses is not None:
                    pg, vl, ent = last_losses
                    aggregator.update("Loss/policy_loss", pg)
                    aggregator.update("Loss/value_loss", vl)
                    aggregator.update("Loss/entropy_loss", ent)
                extra = dict(traj_queue.metrics())
                extra.update(broadcast.metrics())
                extra["Sebulba/traj_staleness_max"] = float(staleness_max)
                extra["Sebulba/traj_staleness_avg"] = (
                    staleness_sum / max(segments_consumed, 1)
                )
                extra["Sebulba/actor_idle_frac"] = float(
                    np.mean([eng.actor_idle_frac() for eng in engines])
                )
                last_log = flush_metrics(
                    aggregator, timer, logger, policy_step, last_log, extra_metrics=extra
                )

            if ckpt_mgr.should_save(policy_step, last_checkpoint, final=update == total_iters):
                last_checkpoint = policy_step
                save_checkpoint()
            if ckpt_mgr.preempted:
                fabric.print(f"Preemption: committed checkpoint at step {policy_step}, exiting")
                break
    finally:
        # unregister on EVERY exit (timeout/staleness/engine errors
        # included): a leaked source would pin the dead run's queue ring
        # and report its stale gauges into the next run's flushes
        HUB.unregister("sebulba.traj_queue")
        HUB.unregister("sebulba.broadcast")
        shutdown(stop_event, traj_queue, obs_queue, engines, supervisor)

    run_stats = collect_run_stats(
        updates=updates_done, env_steps=env_steps_consumed,
        engines=engines, traj_queue=traj_queue, broadcast=broadcast,
        traj_staleness_max=staleness_max, supervisor=supervisor,
    )

    ckpt_mgr.finalize()
    if cfg.algo.run_test and not ckpt_mgr.preempted:
        test(agent, fabric.to_host(params), cfg, log_dir, logger)
    if logger is not None:
        logger.close()
    return run_stats
