"""Sebulba SAC: the decoupled SAC loop rebuilt on the actor–learner device
split (``topology=sebulba``; docs/sebulba.md).

Same skeleton as :mod:`sheeprl_tpu.sebulba.ppo`, with the off-policy
differences:

* env workers push fixed-length **transition segments**
  (``topology.segment_steps`` × per-worker envs of ``obs/next_obs/actions/
  rewards/terminated`` rows) — the trajectory queue stays host-side
  (``stage=False``) because the learner's device-resident store is the
  :class:`~sheeprl_tpu.data.device_replay.DeviceReplay` HBM ring itself,
  sharded over the **learner sub-mesh**; the queue contributes ordering +
  backpressure + staleness metadata only;
* the learner appends consumed segments into the ring and runs the
  ``Ratio``-owed gradient steps through ``fused_uniform_train`` (sampling
  compiled into the update dispatch — PR 9's zero-copy path, now scoped to
  the learner device group);
* only the ACTOR subtree of the params is broadcast to the actor devices
  (the critic never leaves the learner group) — the Sebulba analogue of
  ``sac_decoupled``'s every-``sync_every``-windows weight refresh.

Workers take uniform random actions until their share of
``algo.learning_starts`` env steps is collected (the coupled loop's
prefill, decentralized per worker).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Tuple

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.sac.agent import build_agent
from sheeprl_tpu.algos.sac.sac import make_sac_train_fns
from sheeprl_tpu.algos.sac.utils import prepare_obs, test
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.device_replay import (
    build_device_replay,
    fused_uniform_train,
    resolve_device_replay,
    sampled_bytes,
    update_chunks,
)
from sheeprl_tpu.parallel.topology import DeviceTopology, ParamBroadcast, topology_cfg
from sheeprl_tpu.sebulba.actor import ActorEngine, derive_ladder
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
from sheeprl_tpu.utils.optim import build_optimizer
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs


class SACWorkerProtocol:
    """Per-step semantics of a SAC env worker: flattened-vector blocks out,
    tanh-squashed actions back; uniform random prefill until this worker's
    share of ``learning_starts`` is collected; ``next_obs`` rows carry the
    TRUE final observation on done envs (autoreset replaced them)."""

    def __init__(self, mlp_keys, act_space: gym.spaces.Box, prefill_steps: int):
        self.mlp_keys = tuple(mlp_keys)
        self.act_low = np.asarray(act_space.low, np.float32)
        self.act_high = np.asarray(act_space.high, np.float32)
        self.act_shape = act_space.shape
        self.prefill_steps = int(prefill_steps)

    def to_env_actions(self, a: np.ndarray) -> np.ndarray:
        return self.act_low + (a + 1.0) * 0.5 * (self.act_high - self.act_low)

    def _random_actions(self, rng: np.random.Generator, n: int) -> np.ndarray:
        env_actions = rng.uniform(self.act_low, self.act_high, (n,) + self.act_shape)
        span = self.act_high - self.act_low
        return np.clip(
            2.0 * (env_actions - self.act_low) / np.where(span == 0, 1, span) - 1.0, -1, 1
        ).astype(np.float32).reshape(n, -1)

    def on_reset(self, worker: EnvWorker, obs) -> None:
        worker._rng = np.random.default_rng(worker.seed)

    def run_segment(
        self, worker: EnvWorker, envs: Any, obs: Dict[str, np.ndarray], steps: int
    ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray], List[Tuple[float, int]], int]:
        num_envs = envs.num_envs
        rows: Dict[str, List[np.ndarray]] = {
            k: [] for k in ("obs", "next_obs", "actions", "rewards", "terminated")
        }
        ep_stats: List[Tuple[float, int]] = []
        obs_vec = np.asarray(prepare_obs(obs, self.mlp_keys))
        for _ in range(steps):
            worker.beat()
            if worker.env_steps + len(rows["obs"]) * num_envs < self.prefill_steps:
                actions = self._random_actions(worker._rng, num_envs)
            else:
                out = worker.infer({"obs": obs_vec})
                actions = np.asarray(out["actions"]).reshape(num_envs, -1)
            next_obs, rewards, terminated, truncated, info = envs.step(
                self.to_env_actions(actions)
            )
            dones = np.logical_or(terminated, truncated).astype(np.float32)
            rewards = np.asarray(rewards, np.float32)
            next_vec = np.asarray(prepare_obs(next_obs, self.mlp_keys))
            store_next = next_vec
            done_idx = np.nonzero(dones)[0]
            if done_idx.size:
                final = final_obs_rows(info, done_idx, self.mlp_keys)
                if final is not None:
                    store_next = next_vec.copy()
                    store_next[done_idx] = np.concatenate(
                        [
                            np.asarray(final[k], np.float32).reshape(done_idx.size, -1)
                            for k in self.mlp_keys
                        ],
                        axis=-1,
                    )
            rows["obs"].append(obs_vec)
            rows["next_obs"].append(store_next)
            rows["actions"].append(actions.astype(np.float32))
            rows["rewards"].append(rewards.reshape(num_envs, 1))
            rows["terminated"].append(np.asarray(terminated, np.float32).reshape(num_envs, 1))
            obs_vec = next_vec
            obs = next_obs
            ep_stats.extend(episode_stats(info))
        segment = {k: np.stack(v, axis=0) for k, v in rows.items()}
        return obs, segment, ep_stats, steps * num_envs


def run_sebulba(fabric: Any, cfg: Any) -> Dict[str, Any]:
    """Train decoupled SAC through the Sebulba topology.  Returns the run's
    counters (``runner.collect_run_stats``)."""
    if fabric.num_processes > 1:
        # multi-process runs split actors and learner across HOSTS, not
        # devices: the in-process topology below assumes one device view
        from sheeprl_tpu.sebulba.pod import run_pod

        run_pod(fabric, cfg)
        return {}
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
    segment_steps = max(1, int(topo_cfg.get("segment_steps", 16)))
    num_workers = max(1, int(topo_cfg.get("env_workers", 2)))
    if num_envs % num_workers:
        raise ValueError(
            f"sebulba env workers need env.num_envs ({num_envs}) divisible "
            f"by topology.env_workers ({num_workers})"
        )
    envs_per_worker = num_envs // num_workers

    probe = make_env(cfg, cfg.seed, 0, run_name=log_dir, vector_env_idx=0)()
    obs_space, act_space = probe.observation_space, probe.action_space
    probe.close()
    if not isinstance(act_space, gym.spaces.Box):
        raise ValueError("SAC supports continuous (Box) action spaces only, like the reference")
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    for k in mlp_keys:
        if k not in obs_space.spaces:
            raise ValueError(f"mlp key '{k}' not in observation space {list(obs_space.spaces)}")
    obs_dim = int(sum(np.prod(obs_space[k].shape) for k in mlp_keys))
    act_dim = int(np.prod(act_space.shape))

    # ---------------- learner: agent + train program -------------------------
    state: Dict[str, Any] = {}
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)
    if state and state.get("key") is not None:
        key = jnp.asarray(state["key"])
    actor, critic, params = build_agent(learner_fab, act_dim, cfg, obs_dim, state.get("agent"))
    actor_opt = build_optimizer(cfg.algo.actor.optimizer)
    critic_opt = build_optimizer(cfg.algo.critic.optimizer)
    alpha_opt = build_optimizer(cfg.algo.alpha.optimizer)
    opt_state = learner_fab.replicate(
        state.get("opt_state")
        or {
            "actor": actor_opt.init(params["actor"]),
            "critic": critic_opt.init(params["critic"]),
            "alpha": alpha_opt.init(params["log_alpha"]),
        }
    )

    def plain_apply(critic_mod, cp, o, a, k):
        return critic_mod.apply(cp, o, a)

    act_fn, train_phase = make_sac_train_fns(
        actor, critic, plain_apply, actor_opt, critic_opt, alpha_opt, cfg, act_dim
    )

    # ---------------- device-resident replay on the learner sub-mesh ---------
    capacity = int(cfg.buffer.size) // num_envs
    memmap_dir = os.path.join(log_dir, "memmap_buffer", "rank_0") if cfg.buffer.memmap else None
    use_device_replay = resolve_device_replay(cfg, fabric.accelerator)
    batch_size = int(cfg.algo.per_rank_batch_size) * learner_fab.local_world_size
    train_phase_dev = None
    if use_device_replay:
        def _prep_batch(b):
            return {
                "obs": b["obs"],
                "next_obs": b["next_obs"],
                "actions": b["actions"],
                "rewards": b["rewards"][..., 0],
                "terminated": b["terminated"][..., 0],
            }

        def _make_fused(ring):
            return fused_uniform_train(
                learner_fab,
                train_phase,
                ring,
                batch_size,
                _prep_batch,
                name=f"{cfg.algo.name}.sebulba_train_phase_device",
                max_recompiles=cfg.algo.get("max_recompiles"),
            )

        # the ring's rows, exactly as the env workers' segments carry them
        leaf_specs = {
            "obs": ((obs_dim,), np.float32),
            "next_obs": ((obs_dim,), np.float32),
            "actions": ((act_dim,), np.float32),
            "rewards": ((1,), np.float32),
            "terminated": ((1,), np.float32),
        }
        # what Ratio will owe at the first train window: every step collected
        # up to learning_starts, in whole rounds
        steps_per_round = num_envs * max(1, int(topo_cfg.get("segment_steps", 16)))
        first_steps = 0 if cfg.dry_run else int(cfg.algo.learning_starts)
        first_steps = max(-(-first_steps // steps_per_round), 1) * steps_per_round
        burst = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)(
            first_steps / learner_fab.world_size
        )
        rb, train_phase_dev = build_device_replay(
            learner_fab, cfg, capacity, num_envs, leaf_specs, _make_fused,
            train_state=(params, opt_state), first_window=burst,
            batch_bytes=sampled_bytes(leaf_specs, batch_size), memmap_dir=memmap_dir,
        )
    else:
        rb = ReplayBuffer(capacity, num_envs, memmap=cfg.buffer.memmap, memmap_dir=memmap_dir)
    if state and cfg.buffer.checkpoint and "rb" in state:
        rb.load_state_dict(state["rb"])

    # ---------------- broadcast + queues + actors ----------------------------
    broadcast = ParamBroadcast(
        fabric,
        topo.actor_devices,
        extract=lambda p: p["actor"],
        max_staleness=int(topo_cfg.get("max_staleness", 2)),
        gate_timeout_s=float(topo_cfg.get("queue_timeout_s", 300.0)),
    )
    sync_every = max(1, int(topo_cfg.get("sync_every", 1)))
    traj_queue = TrajQueue(
        clamp_queue_slots(topo_cfg, num_workers),
        segment_steps,
        learner_fab,
        stage=False,  # the device-resident store is the DeviceReplay ring
        timeout_s=float(topo_cfg.get("queue_timeout_s", 300.0)),
    )
    stats_sink = StatsSink()
    stop_event = threading.Event()
    obs_queue = ObsQueue(max_pending=2 * num_workers)
    ladder = derive_ladder(envs_per_worker, num_workers, topo_cfg.get("actor_batch_ladder"))

    def policy_fn(p, obs, k):
        a, k_next = act_fn.jitted(p, obs["obs"], k)
        return {"actions": a}, k_next

    obs_spec = {"obs": ((obs_dim,), np.dtype(np.float32))}
    actor_param_spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params["actor"]
    )
    engines: List[ActorEngine] = []
    for i, dev in enumerate(topo.actor_devices):
        eng = ActorEngine(
            i, dev, policy_fn, obs_spec, actor_param_spec, ladder, envs_per_worker,
            obs_queue, broadcast, jax.random.fold_in(key, 0xF0 + i),
            max_wait_s=float(topo_cfg.get("max_wait_ms", 20.0)) / 1e3,
            max_recompiles=cfg.algo.get("max_recompiles"),
        )
        if cfg.algo.get("compile_warmup", True):
            eng.warmup(fabric.compile_pool, join=False)
        engines.append(eng)
    fabric.compile_pool.join()

    learning_starts = int(cfg.algo.learning_starts) if not cfg.dry_run else 0
    protocol = SACWorkerProtocol(
        mlp_keys, act_space, prefill_steps=-(-learning_starts // num_workers)
    )

    supervisor = build_worker_fleet(
        cfg, topo_cfg,
        protocol=protocol, obs_queue=obs_queue, traj_queue=traj_queue,
        segment_steps=segment_steps, num_workers=num_workers,
        envs_per_worker=envs_per_worker, log_dir=log_dir,
        stop_event=stop_event, stats_sink=stats_sink,
    )

    # ---------------- counters -----------------------------------------------
    aggregator = MetricAggregator(cfg.metric.aggregator.metrics if cfg.metric.log_level > 0 else {})
    timer.configure(cfg.metric)
    steps_per_round = num_envs * segment_steps
    total_rounds = max(int(cfg.algo.total_steps) // steps_per_round, 1)
    if cfg.dry_run:
        total_rounds = 1
    start_round = int(state.get("update", 0)) + 1 if state else 1
    policy_step = int(state.get("policy_step", 0))
    last_log = int(state.get("last_log", 0))
    last_checkpoint = int(state.get("last_checkpoint", 0))
    grad_step_counter = int(state.get("grad_steps", 0))
    windows = int(state.get("windows", 0))
    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state and "ratio" in state:
        ratio.load_state_dict(state["ratio"])

    staleness_sum = 0
    staleness_max = 0
    segments_consumed = 0
    env_steps_consumed = 0
    last_losses = None
    counter_dev = None

    # ---------------- run ----------------------------------------------------
    # live hub sources for the run + a fresh span window so the first flush's
    # phase breakdown covers the training loop (see sebulba/ppo.py)
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
                "update": rnd,
                "policy_step": policy_step,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
                "ratio": ratio.state_dict(),
                "grad_steps": grad_step_counter,
                "windows": windows,
            },
            replay_buffer=rb if cfg.buffer.checkpoint else None,
        )

    try:
        # inside the try: the first publish crosses fabric.copy_to (a
        # fault-injection site) — a throw here must still unregister
        broadcast.publish(params, version=windows)
        for eng in engines:
            eng.start()
        supervisor.start()
        rnd = start_round - 1
        for rnd in range(start_round, total_rounds + 1):
            with timer("Time/env_interaction_time"):
                items = drain_preemptible(
                    traj_queue, num_workers, engines, supervisor,
                    ckpt_mgr=ckpt_mgr, fabric=fabric, policy_step=policy_step,
                    save_checkpoint=save_checkpoint,
                )
            if items is None:  # preempted mid-wait: committed save done
                break
            for seg, meta in items:
                base = int(meta.get("worker", 0)) * envs_per_worker
                rb.add(
                    {k: np.asarray(v) for k, v in seg.items()},
                    indices=range(base, base + envs_per_worker),
                )
                lag = broadcast.version - int(meta.get("version", 0))
                staleness_sum += lag
                staleness_max = max(staleness_max, lag)
                env_steps_consumed += int(meta.get("env_steps", 0))
            segments_consumed += len(items)
            policy_step += steps_per_round

            if policy_step >= learning_starts:
                gradient_steps = ratio(policy_step / learner_fab.world_size)
                if gradient_steps > 0:
                    windows += 1
                    with timer("Time/train_time"):
                        if train_phase_dev is not None:
                            if counter_dev is None:
                                counter_dev = learner_fab.replicate(np.int32(grad_step_counter))
                            for u in update_chunks(
                                gradient_steps,
                                bytes_per_update=rb.sampled_bytes_per_update(batch_size),
                            ):
                                key, tk = jax.random.split(key)
                                params, opt_state, counter_dev, last_losses = train_phase_dev(
                                    params, opt_state, rb.buffers, rb.cursor, tk,
                                    counter_dev, n_samples=u,
                                )
                                grad_step_counter += u
                        else:
                            sample = rb.sample(batch_size, n_samples=gradient_steps)
                            batches = {
                                "obs": jnp.asarray(sample["obs"]),
                                "next_obs": jnp.asarray(sample["next_obs"]),
                                "actions": jnp.asarray(sample["actions"]),
                                "rewards": jnp.asarray(sample["rewards"][..., 0]),
                                "terminated": jnp.asarray(sample["terminated"][..., 0]),
                            }
                            batches = learner_fab.shard_batch(batches, axis=1)
                            key, tk = jax.random.split(key)
                            params, opt_state, last_losses = train_phase(
                                params, opt_state, batches, tk, jnp.int32(grad_step_counter)
                            )
                            grad_step_counter += gradient_steps
                    if windows % sync_every == 0:
                        broadcast.publish(params, version=windows)
                        broadcast.gate()
            supervisor.check()

            if cfg.metric.log_level > 0 and (
                policy_step - last_log >= cfg.metric.log_every or rnd == total_rounds or cfg.dry_run
            ):
                for ep_ret, ep_len in stats_sink.drain():
                    aggregator.update("Rewards/rew_avg", float(ep_ret))
                    aggregator.update("Game/ep_len_avg", int(ep_len))
                if last_losses is not None:
                    vl, pl, al = last_losses
                    aggregator.update("Loss/value_loss", vl)
                    aggregator.update("Loss/policy_loss", pl)
                    aggregator.update("Loss/alpha_loss", al)
                extra = dict(traj_queue.metrics())
                extra.update(broadcast.metrics())
                extra["Sebulba/traj_staleness_max"] = float(staleness_max)
                extra["Sebulba/traj_staleness_avg"] = staleness_sum / max(segments_consumed, 1)
                extra["Sebulba/actor_idle_frac"] = float(
                    np.mean([eng.actor_idle_frac() for eng in engines])
                )
                extra["Params/replay_ratio"] = (
                    grad_step_counter * learner_fab.world_size / max(policy_step, 1)
                )
                last_log = flush_metrics(
                    aggregator, timer, logger, policy_step, last_log, extra_metrics=extra
                )

            if ckpt_mgr.should_save(policy_step, last_checkpoint, final=rnd == total_rounds):
                last_checkpoint = policy_step
                save_checkpoint()
            if ckpt_mgr.preempted:
                fabric.print(f"Preemption: committed checkpoint at step {policy_step}, exiting")
                break
    finally:
        # unregister on EVERY exit — a leaked source would pin the dead
        # run's queue ring and report stale gauges into the next run
        HUB.unregister("sebulba.traj_queue")
        HUB.unregister("sebulba.broadcast")
        shutdown(stop_event, traj_queue, obs_queue, engines, supervisor)

    run_stats = collect_run_stats(
        updates=windows, env_steps=env_steps_consumed,
        engines=engines, traj_queue=traj_queue, broadcast=broadcast,
        traj_staleness_max=staleness_max, supervisor=supervisor,
    )

    if getattr(rb, "spill", None) is not None:
        rb.spill.close()
    ckpt_mgr.finalize()
    if cfg.algo.run_test and not ckpt_mgr.preempted:
        test(actor, fabric.to_host(params["actor"]), cfg, log_dir, logger)
    if logger is not None:
        logger.close()
    return run_stats
