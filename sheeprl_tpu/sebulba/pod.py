"""The pod driver: Sebulba stretched across hosts (``topology=pod``).

One process (rank 0) is the **learner cell**; every other process is an
**actor cell** (:class:`~sheeprl_tpu.parallel.topology.PodTopology` — the
process boundary IS the actor/learner split).  Each cell computes only on
its own local devices through a 1-D local fabric; nothing in the
steady-state data path crosses hosts through XLA collectives.  Instead:

* **segments** — every actor cell runs the ordinary Sebulba machinery
  (per-device :class:`~sheeprl_tpu.sebulba.actor.ActorEngine` inference +
  the env-worker fleet) into a host-side :class:`~sheeprl_tpu.sebulba.
  queues.TrajQueue`; a pusher thread ships each segment to the learner
  front CRC-stamped (``sebulba/transport.py``) under the identical
  never-drop / torn-segment-reject contract the in-process queue enforces;
* **params** — the learner publishes through
  :class:`~sheeprl_tpu.sebulba.transport.DcnParamBroadcast` (same
  versioned ``max_staleness`` gate, serialized transport); actor cells
  fetch over HTTP, verify the CRC, and republish onto their local devices
  through a plain in-process ``ParamBroadcast``;
* **control** — commit-step announcements, coordinated preemption (either
  side's SIGTERM latch preempts the whole pod), liveness (transport
  heartbeats + the :class:`~sheeprl_tpu.parallel.distributed.PeerWatchdog`
  KV heartbeat hard-stop), and per-cell telemetry snapshots ride the
  ``/poll`` loop.

Checkpointing: the per-rank shard + COMMIT-last protocol
(``checkpoint/protocol.py``) is the pod's recovery substrate.  The
learner announces each save's step over the control plane BEFORE writing
its own shard; every actor cell writes its shard into the same step
directory when its next poll observes the step, and rank 0's commit waits
for all ``fabric.num_processes`` shards — so a committed snapshot always
represents the whole pod, and the pod supervisor restarts every rank from
the newest shared commit.
"""

from __future__ import annotations

import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.checkpoint.preemption import PREEMPTION_GUARD
from sheeprl_tpu.checkpoint.protocol import probe_shared_root, step_dir_name, write_shard
from sheeprl_tpu.parallel.distributed import PeerWatchdog, distributed_cfg
from sheeprl_tpu.parallel.topology import ParamBroadcast, PodTopology, topology_cfg
from sheeprl_tpu.sebulba.actor import ActorEngine, derive_ladder
from sheeprl_tpu.sebulba.queues import ObsQueue, ServiceStopped, TrajQueue
from sheeprl_tpu.sebulba.runner import (
    StatsSink,
    arm_preemption,
    build_worker_fleet,
    clamp_queue_slots,
    drain_preemptible,
    shutdown,
)
from sheeprl_tpu.sebulba.transport import (
    DcnParamBroadcast,
    LearnerFront,
    PodClient,
    lookup_front_address,
    publish_front_address,
)
from sheeprl_tpu.telemetry import HUB, SPANS
from sheeprl_tpu.utils.env import make_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, flush_metrics
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import polynomial_decay, save_configs

def _pod_knobs(cfg: Any) -> Tuple[Dict[str, Any], Dict[str, Any], Dict[str, Any]]:
    topo_cfg = topology_cfg(cfg)
    return topo_cfg, dict(topo_cfg.get("pod") or {}), distributed_cfg(cfg)


def _split_envs(cfg: Any, topo: PodTopology, topo_cfg: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """``(num_envs, envs_per_cell, env_workers, envs_per_worker)`` — the
    global env count divided first across actor cells, then across each
    cell's worker fleet."""
    num_envs = int(cfg.env.num_envs)
    cells = topo.num_actor_cells
    if num_envs % cells:
        raise ValueError(
            f"pod topology needs env.num_envs ({num_envs}) divisible by the "
            f"{cells} actor cells"
        )
    envs_per_cell = num_envs // cells
    env_workers = max(1, int(topo_cfg.get("env_workers", 2)))
    if envs_per_cell % env_workers:
        raise ValueError(
            f"pod topology needs per-cell envs ({envs_per_cell}) divisible "
            f"by topology.env_workers ({env_workers})"
        )
    return num_envs, envs_per_cell, env_workers, envs_per_cell // env_workers


def _start_watchdog(fabric: Any, dist: Dict[str, Any]) -> Optional[PeerWatchdog]:
    """The KV heartbeat hard-stop: even if this cell's main thread is
    wedged inside a dispatch, a dead peer forces the process down within
    ``heartbeat_grace_s`` + the hard-exit delay — no rank trains past a
    dead peer, and exit code 75 tells the pod supervisor to restart."""
    if not bool(dist.get("watchdog", True)):
        return None
    try:
        return PeerWatchdog(
            fabric.global_rank,
            fabric.num_processes,
            heartbeat_s=float(dist.get("heartbeat_s", 1.0)),
            grace_s=float(dist.get("heartbeat_grace_s", 30.0)),
        ).start()
    except RuntimeError:
        return None  # KV client unavailable (tests with hand-built fabrics)


def run_pod(fabric: Any, cfg: Any) -> None:
    """Train through the cross-host pod topology.  Dispatches on this
    process's role; both roles run the identical preamble (seed, run-dir
    agreement, telemetry arm) so the fabric's host-collective sequence
    stays aligned across the pod."""
    topo = PodTopology.from_config(fabric, cfg)
    fabric.print(topo.describe())
    key = fabric.seed_everything(cfg.seed)
    log_dir = get_log_dir(fabric, cfg.root_dir, cfg.run_name, base=cfg.get("log_dir", "logs/runs"))
    logger = get_logger(fabric, cfg, log_dir)

    algo = str(cfg.algo.name)
    if "ppo" in algo:
        flavor = "ppo"
    elif "sac" in algo:
        flavor = "sac"
    else:
        raise ValueError(f"topology=pod supports the decoupled ppo/sac drivers, not {algo!r}")

    _, _, dist = _pod_knobs(cfg)
    watchdog = _start_watchdog(fabric, dist)
    try:
        if topo.role == "learner":
            save_configs(cfg, log_dir)
            learner = _learner_ppo if flavor == "ppo" else _learner_sac
            learner(fabric, cfg, topo, key=key, log_dir=log_dir, logger=logger)
            return
        HUB.set_namespace(f"rank{topo.process_index}")
        try:
            actor = _actor_ppo if flavor == "ppo" else _actor_sac
            actor(fabric, cfg, topo, key=key, log_dir=log_dir)
        finally:
            HUB.set_namespace(None)
    finally:
        if watchdog is not None:
            watchdog.stop()


# ---------------------------------------------------------------------------
# learner cells
# ---------------------------------------------------------------------------


def _learner_transport(
    cfg: Any,
    topo: PodTopology,
    traj_queue: TrajQueue,
    broadcast: DcnParamBroadcast,
) -> LearnerFront:
    _, pod, dist = _pod_knobs(cfg)
    front = LearnerFront(
        traj_queue,
        broadcast,
        topo.actor_cells,
        port=int(pod.get("port", 0) or 0),
        heartbeat_grace_s=float(dist.get("heartbeat_grace_s", 30.0)),
        first_contact_grace_s=float(pod.get("first_contact_grace_s", 300.0)),
    ).start()
    publish_front_address(front.address)
    return front


def _finish_learner(
    fabric: Any, ckpt_mgr: Any, front: LearnerFront, traj_queue: TrajQueue
) -> None:
    """Teardown in commit order: drain pending async saves FIRST (rank 0's
    commit waits for the actor shards, which arrive while the actors are
    still polling), then release the actors with ``done`` and collect
    their goodbyes before the front goes away."""
    try:
        ckpt_mgr.flush()
    finally:
        front.set_done()
        front.wait_goodbyes(timeout_s=30.0)
        front.stop()
        traj_queue.close()


def _learner_ppo(
    fabric: Any, cfg: Any, topo: PodTopology, *, key: Any, log_dir: str, logger: Any
) -> None:
    """The decoupled-PPO learner cell: ``sebulba/ppo.py``'s learner half
    with the local actor fleet replaced by the DCN front."""
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo_decoupled import _build_train_fns
    from sheeprl_tpu.algos.ppo.utils import normalize_obs_keys, spaces_to_dims, test
    from sheeprl_tpu.utils.optim import build_optimizer, set_learning_rate

    topo_cfg, pod, _ = _pod_knobs(cfg)
    learner_fab = topo.cell_fabric
    ckpt_mgr = fabric.get_checkpoint_manager(cfg, log_dir)
    # pod cells do not iterate in lockstep: the collective preemption poll
    # and the post-save barrier would hang against cells that never call
    # them — agreement arrives over the control plane instead
    ckpt_mgr.lockstep = False

    num_envs, _, env_workers, _ = _split_envs(cfg, topo, topo_cfg)
    rollout_steps = int(cfg.algo.rollout_steps)
    n_producers = topo.num_actor_cells * env_workers

    probe = make_env(cfg, cfg.seed, 0, run_name=log_dir, vector_env_idx=0)()
    obs_space, act_space = probe.observation_space, probe.action_space
    probe.close()
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
        key = jnp.asarray(state["key"])
    agent, params = build_agent(
        learner_fab, actions_dim, is_continuous, cfg, obs_space, state.get("agent")
    )
    optimizer = build_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm)
    opt_state = learner_fab.replicate(state.get("opt_state") or optimizer.init(params))

    _, _, _, train_phase_raw = _build_train_fns(
        agent, optimizer, cfg, obs_keys, actions_dim, is_continuous, dist_type, obs_space
    )

    T, B = rollout_steps, num_envs
    global_bs = min(int(cfg.algo.per_rank_batch_size) * learner_fab.world_size, T * B)
    num_minibatches = -(-T * B // global_bs)

    def learner_phase(p, o_state, segs, k, clip_coef, ent_coef):
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

    learner_phase = learner_fab.compile(
        learner_phase,
        name=f"{cfg.algo.name}.pod_learner_phase",
        donate_argnums=(0, 1),
        max_recompiles=cfg.algo.get("max_recompiles"),
    )

    broadcast = DcnParamBroadcast(
        topo.actor_cells,
        extract=lambda p: jax.device_get(p),
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
    front = _learner_transport(cfg, topo, traj_queue, broadcast)

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
    last_losses = None

    HUB.register("sebulba.traj_queue", traj_queue.metrics)
    HUB.register("dcn.front", front.metrics)
    SPANS.roll_window()
    arm_preemption(cfg)

    def save_checkpoint() -> None:
        # the step announcement goes out FIRST: actor cells write their
        # shards into step_dir(policy_step) while the learner's own shard
        # is written, and rank 0's commit waits for all of them
        front.set_commit(policy_step)
        fabric.call(
            "on_checkpoint_player",
            ckpt_path=str(Path(log_dir) / "checkpoint" / f"ckpt_{policy_step}_0.ckpt"),
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
        broadcast.publish(params, version=start_iter - 1)
        front.wait_for_cells(timeout_s=float(pod.get("first_contact_grace_s", 300.0)))
        update = start_iter - 1
        for update in range(start_iter, total_iters + 1):
            with timer("Time/env_interaction_time"):
                items = drain_preemptible(
                    traj_queue, n_producers, [front], None,
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
            segments_consumed += len(items)
            policy_step += policy_steps_per_iter

            with timer("Time/train_time"):
                key, tk = jax.random.split(key)
                params, opt_state, last_losses = learner_phase(
                    params, opt_state, segs, tk,
                    jnp.float32(clip_coef_v), jnp.float32(ent_coef_v),
                )
            if update % sync_every == 0 or update == total_iters:
                broadcast.publish(params, version=update)
                broadcast.gate()

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
                if last_losses is not None:
                    pg, vl, ent = last_losses
                    aggregator.update("Loss/policy_loss", pg)
                    aggregator.update("Loss/value_loss", vl)
                    aggregator.update("Loss/entropy_loss", ent)
                extra = dict(traj_queue.metrics())
                extra.update(front.metrics())
                extra["Sebulba/traj_staleness_max"] = float(staleness_max)
                extra["Sebulba/traj_staleness_avg"] = staleness_sum / max(segments_consumed, 1)
                last_log = flush_metrics(
                    aggregator, timer, logger, policy_step, last_log, extra_metrics=extra
                )

            # coordinated preemption, DCN direction actor → learner: an
            # actor cell's SIGTERM latch (surfaced by its poll) preempts
            # the whole pod through the ordinary committed-final-save path
            if front.actor_latched and not ckpt_mgr.preempted:
                fabric.print("Preemption latched on an actor cell: pod-wide final save")
                ckpt_mgr.force_preempt()
            if ckpt_mgr.should_save(policy_step, last_checkpoint, final=update == total_iters):
                last_checkpoint = policy_step
                save_checkpoint()
            if ckpt_mgr.preempted:
                fabric.print(f"Preemption: committed checkpoint at step {policy_step}, exiting")
                break
    finally:
        HUB.unregister("sebulba.traj_queue")
        HUB.unregister("dcn.front")
        _finish_learner(fabric, ckpt_mgr, front, traj_queue)

    ckpt_mgr.finalize()
    if cfg.algo.run_test and not ckpt_mgr.preempted:
        test(agent, fabric.to_host(params), cfg, log_dir, logger)
    if logger is not None:
        logger.close()


def _learner_sac(
    fabric: Any, cfg: Any, topo: PodTopology, *, key: Any, log_dir: str, logger: Any
) -> None:
    """The decoupled-SAC learner cell: ``sebulba/sac.py``'s learner half
    (host replay + the ``Ratio``-owed gradient steps) fed by the front.
    Only the actor subtree crosses the DCN, as in-process."""
    import gymnasium as gym

    from sheeprl_tpu.algos.sac.agent import build_agent
    from sheeprl_tpu.algos.sac.sac import make_sac_train_fns
    from sheeprl_tpu.algos.sac.utils import test
    from sheeprl_tpu.data.buffers import ReplayBuffer
    from sheeprl_tpu.utils.optim import build_optimizer
    from sheeprl_tpu.utils.utils import Ratio

    topo_cfg, pod, _ = _pod_knobs(cfg)
    learner_fab = topo.cell_fabric
    ckpt_mgr = fabric.get_checkpoint_manager(cfg, log_dir)
    ckpt_mgr.lockstep = False

    num_envs, _, env_workers, envs_per_worker = _split_envs(cfg, topo, topo_cfg)
    segment_steps = max(1, int(topo_cfg.get("segment_steps", 16)))
    n_producers = topo.num_actor_cells * env_workers

    probe = make_env(cfg, cfg.seed, 0, run_name=log_dir, vector_env_idx=0)()
    obs_space, act_space = probe.observation_space, probe.action_space
    probe.close()
    if not isinstance(act_space, gym.spaces.Box):
        raise ValueError("SAC supports continuous (Box) action spaces only, like the reference")
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs_dim = int(sum(np.prod(obs_space[k].shape) for k in mlp_keys))
    act_dim = int(np.prod(act_space.shape))

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

    _, train_phase = make_sac_train_fns(
        actor, critic, plain_apply, actor_opt, critic_opt, alpha_opt, cfg, act_dim
    )

    # host-side replay on the learner cell (the DCN pod's segments arrive
    # as host numpy; the single-host driver's DeviceReplay HBM ring is an
    # orthogonal optimization the cell can adopt later)
    capacity = int(cfg.buffer.size) // num_envs
    memmap_dir = str(Path(log_dir) / "memmap_buffer" / "rank_0") if cfg.buffer.memmap else None
    rb = ReplayBuffer(capacity, num_envs, memmap=cfg.buffer.memmap, memmap_dir=memmap_dir)
    if state and cfg.buffer.checkpoint and "rb" in state:
        rb.load_state_dict(state["rb"])
    batch_size = int(cfg.algo.per_rank_batch_size) * learner_fab.local_world_size

    broadcast = DcnParamBroadcast(
        topo.actor_cells,
        extract=lambda p: jax.device_get(p["actor"]),
        max_staleness=int(topo_cfg.get("max_staleness", 2)),
        gate_timeout_s=float(topo_cfg.get("queue_timeout_s", 300.0)),
    )
    sync_every = max(1, int(topo_cfg.get("sync_every", 1)))
    traj_queue = TrajQueue(
        clamp_queue_slots(topo_cfg, n_producers),
        segment_steps,
        learner_fab,
        stage=False,  # payloads land in the host replay ring
        timeout_s=float(topo_cfg.get("queue_timeout_s", 300.0)),
    )
    front = _learner_transport(cfg, topo, traj_queue, broadcast)

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
    learning_starts = int(cfg.algo.learning_starts) if not cfg.dry_run else 0
    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state and "ratio" in state:
        ratio.load_state_dict(state["ratio"])

    staleness_sum = 0
    staleness_max = 0
    segments_consumed = 0
    last_losses = None

    HUB.register("sebulba.traj_queue", traj_queue.metrics)
    HUB.register("dcn.front", front.metrics)
    SPANS.roll_window()
    arm_preemption(cfg)

    def save_checkpoint() -> None:
        front.set_commit(policy_step)
        fabric.call(
            "on_checkpoint_player",
            ckpt_path=str(Path(log_dir) / "checkpoint" / f"ckpt_{policy_step}_0.ckpt"),
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
        broadcast.publish(params, version=windows)
        front.wait_for_cells(timeout_s=float(pod.get("first_contact_grace_s", 300.0)))
        rnd = start_round - 1
        for rnd in range(start_round, total_rounds + 1):
            with timer("Time/env_interaction_time"):
                items = drain_preemptible(
                    traj_queue, n_producers, [front], None,
                    ckpt_mgr=ckpt_mgr, fabric=fabric, policy_step=policy_step,
                    save_checkpoint=save_checkpoint,
                )
            if items is None:
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
            segments_consumed += len(items)
            policy_step += steps_per_round

            if policy_step >= learning_starts:
                gradient_steps = ratio(policy_step / learner_fab.world_size)
                if gradient_steps > 0:
                    windows += 1
                    with timer("Time/train_time"):
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

            if cfg.metric.log_level > 0 and (
                policy_step - last_log >= cfg.metric.log_every or rnd == total_rounds or cfg.dry_run
            ):
                if last_losses is not None:
                    vl, pl, al = last_losses
                    aggregator.update("Loss/value_loss", vl)
                    aggregator.update("Loss/policy_loss", pl)
                    aggregator.update("Loss/alpha_loss", al)
                extra = dict(traj_queue.metrics())
                extra.update(front.metrics())
                extra["Sebulba/traj_staleness_max"] = float(staleness_max)
                extra["Sebulba/traj_staleness_avg"] = staleness_sum / max(segments_consumed, 1)
                last_log = flush_metrics(
                    aggregator, timer, logger, policy_step, last_log, extra_metrics=extra
                )

            if front.actor_latched and not ckpt_mgr.preempted:
                fabric.print("Preemption latched on an actor cell: pod-wide final save")
                ckpt_mgr.force_preempt()
            if ckpt_mgr.should_save(policy_step, last_checkpoint, final=rnd == total_rounds):
                last_checkpoint = policy_step
                save_checkpoint()
            if ckpt_mgr.preempted:
                fabric.print(f"Preemption: committed checkpoint at step {policy_step}, exiting")
                break
    finally:
        HUB.unregister("sebulba.traj_queue")
        HUB.unregister("dcn.front")
        _finish_learner(fabric, ckpt_mgr, front, traj_queue)

    ckpt_mgr.finalize()
    if cfg.algo.run_test and not ckpt_mgr.preempted:
        test(actor, fabric.to_host(params["actor"]), cfg, log_dir, logger)
    if logger is not None:
        logger.close()


# ---------------------------------------------------------------------------
# actor cells
# ---------------------------------------------------------------------------


def _actor_ppo(fabric: Any, cfg: Any, topo: PodTopology, *, key: Any, log_dir: str) -> None:
    from sheeprl_tpu.algos.ppo.agent import build_agent, sample_actions
    from sheeprl_tpu.algos.ppo.utils import normalize_obs_keys, spaces_to_dims
    from sheeprl_tpu.sebulba.ppo import PPOWorkerProtocol

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

    # the module (apply fn) only — the weights themselves arrive from the
    # learner's first broadcast before any engine starts
    agent, _ = build_agent(topo.cell_fabric, actions_dim, is_continuous, cfg, obs_space, None)

    def policy_fn(p, obs, k):
        k_sample, k_next = jax.random.split(k)
        out, value = agent.apply(p, obs)
        actions, logprob, _ = sample_actions(
            out, actions_dim, is_continuous, k_sample, dist_type=dist_type
        )
        return {"actions": actions, "logprobs": logprob, "values": value[..., 0]}, k_next

    protocol = PPOWorkerProtocol(obs_keys, cnn_keys, mlp_keys, act_space, gamma)
    probe_prep = protocol.prepare(
        {k: np.zeros((1,) + tuple(obs_space[k].shape), obs_space[k].dtype) for k in obs_keys}
    )
    obs_spec = {k: (tuple(v.shape[1:]), v.dtype) for k, v in probe_prep.items()}
    _drive_actor_cell(
        fabric, cfg, topo,
        key=key, log_dir=log_dir,
        protocol=protocol, policy_fn=policy_fn, obs_spec=obs_spec,
        segment_steps=int(cfg.algo.rollout_steps),
        bootstrap_keys=tuple(f"last_{k}" for k in obs_keys),
    )


def _actor_sac(fabric: Any, cfg: Any, topo: PodTopology, *, key: Any, log_dir: str) -> None:
    import gymnasium as gym

    from sheeprl_tpu.algos.sac.agent import build_agent
    from sheeprl_tpu.algos.sac.sac import make_sac_train_fns
    from sheeprl_tpu.sebulba.sac import SACWorkerProtocol
    from sheeprl_tpu.utils.optim import build_optimizer

    topo_cfg, _, _ = _pod_knobs(cfg)
    probe = make_env(cfg, cfg.seed, 0, run_name=log_dir, vector_env_idx=0)()
    obs_space, act_space = probe.observation_space, probe.action_space
    probe.close()
    if not isinstance(act_space, gym.spaces.Box):
        raise ValueError("SAC supports continuous (Box) action spaces only, like the reference")
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs_dim = int(sum(np.prod(obs_space[k].shape) for k in mlp_keys))
    act_dim = int(np.prod(act_space.shape))

    actor, critic, _ = build_agent(topo.cell_fabric, act_dim, cfg, obs_dim, None)
    actor_opt = build_optimizer(cfg.algo.actor.optimizer)
    critic_opt = build_optimizer(cfg.algo.critic.optimizer)
    alpha_opt = build_optimizer(cfg.algo.alpha.optimizer)

    def plain_apply(critic_mod, cp, o, a, k):
        return critic_mod.apply(cp, o, a)

    act_fn, _ = make_sac_train_fns(
        actor, critic, plain_apply, actor_opt, critic_opt, alpha_opt, cfg, act_dim
    )

    def policy_fn(p, obs, k):
        a, k_next = act_fn.jitted(p, obs["obs"], k)
        return {"actions": a}, k_next

    _, _, env_workers, _ = _split_envs(cfg, topo, topo_cfg)
    learning_starts = int(cfg.algo.learning_starts) if not cfg.dry_run else 0
    global_workers = topo.num_actor_cells * env_workers
    protocol = SACWorkerProtocol(
        mlp_keys, act_space, prefill_steps=-(-learning_starts // global_workers)
    )
    _drive_actor_cell(
        fabric, cfg, topo,
        key=key, log_dir=log_dir,
        protocol=protocol, policy_fn=policy_fn,
        obs_spec={"obs": ((obs_dim,), np.dtype(np.float32))},
        segment_steps=max(1, int(topo_cfg.get("segment_steps", 16))),
        bootstrap_keys=(),
    )


def _drive_actor_cell(
    fabric: Any,
    cfg: Any,
    topo: PodTopology,
    *,
    key: Any,
    log_dir: str,
    protocol: Any,
    policy_fn: Any,
    obs_spec: Dict[str, Any],
    segment_steps: int,
    bootstrap_keys: Tuple[str, ...],
) -> None:
    """The algorithm-agnostic actor cell: local inference engines + env
    workers into a host-side queue; a pusher thread ships segments over
    the DCN; the main thread runs the ``/poll`` control loop (param
    refresh, shard writes on commit announcements, coordinated exit)."""
    topo_cfg, pod, dist = _pod_knobs(cfg)
    rank = topo.process_index
    cell = topo.cell_index
    ckpt_root = Path(log_dir) / "checkpoint"
    first_contact = float(pod.get("first_contact_grace_s", 300.0))
    # fail fast on a host-local checkpoint.root (satellite of the commit
    # protocol: rank 0's probe marker must be visible from every cell)
    probe_shared_root(ckpt_root, rank, timeout_s=min(60.0, first_contact))

    _, envs_per_cell, env_workers, envs_per_worker = _split_envs(cfg, topo, topo_cfg)
    address = lookup_front_address(timeout_s=first_contact)
    client = PodClient(
        address,
        rank,
        push_deadline_s=float(pod.get("push_deadline_s", 300.0)),
        request_timeout_s=float(pod.get("request_timeout_s", 10.0)),
        heartbeat_grace_s=float(dist.get("heartbeat_grace_s", 30.0)),
    )

    # first params define the broadcast spec: block until the learner's
    # initial publish is fetchable (CRC-verified) so no engine ever runs
    # on randomly-initialized local weights
    deadline = time.monotonic() + first_contact
    fetched = None
    while fetched is None:
        fetched = client.fetch_params(-1)
        if fetched is None:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"pod actor cell {rank}: learner at {address} never "
                    f"published params within {first_contact:g}s"
                )
            time.sleep(0.2)
    host_params, applied = fetched
    param_spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype), host_params
    )
    # the local republish leg: the DCN staleness gate lives at the learner
    # (cursors advance on /poll acks), so the in-cell gate never binds
    broadcast = ParamBroadcast(
        topo.cell_fabric,
        topo.local_devices,
        max_staleness=2**31,
        gate_timeout_s=float(topo_cfg.get("queue_timeout_s", 300.0)),
    )
    broadcast.publish(host_params, version=applied)

    local_queue = TrajQueue(
        clamp_queue_slots(topo_cfg, env_workers),
        segment_steps,
        None,
        stage=False,  # host payloads; the DCN pusher is the consumer
        bootstrap_keys=bootstrap_keys,
        timeout_s=float(topo_cfg.get("queue_timeout_s", 300.0)),
    )
    obs_queue = ObsQueue(max_pending=2 * env_workers)
    ladder = derive_ladder(envs_per_worker, env_workers, topo_cfg.get("actor_batch_ladder"))
    engines: List[ActorEngine] = []
    for i, dev in enumerate(topo.local_devices):
        eng = ActorEngine(
            i, dev, policy_fn, obs_spec, param_spec, ladder, envs_per_worker,
            obs_queue, broadcast, jax.random.fold_in(key, 0xF0 + 16 * rank + i),
            max_wait_s=float(topo_cfg.get("max_wait_ms", 20.0)) / 1e3,
            max_recompiles=cfg.algo.get("max_recompiles"),
        )
        if cfg.algo.get("compile_warmup", True):
            eng.warmup(fabric.compile_pool, join=False)
        engines.append(eng)
    fabric.compile_pool.join()

    stats_sink = StatsSink()
    stop_event = threading.Event()
    supervisor = build_worker_fleet(
        cfg, topo_cfg,
        protocol=protocol, obs_queue=obs_queue, traj_queue=local_queue,
        segment_steps=segment_steps, num_workers=env_workers,
        envs_per_worker=envs_per_worker, log_dir=log_dir,
        stop_event=stop_event, stats_sink=stats_sink,
        env_offset=cell * envs_per_cell,
    )

    pusher_errors: List[BaseException] = []

    def _pusher() -> None:
        try:
            while True:
                try:
                    items = local_queue.get_many(1, timeout_s=1.0)
                except TimeoutError:
                    if stop_event.is_set():
                        return
                    continue
                if not items:
                    if local_queue.closed:
                        return
                    continue
                for seg, meta in items:
                    meta = dict(meta)
                    # worker ids go global so the learner's replay slot
                    # math (SAC) and staleness ledgers see one pod-wide
                    # worker namespace
                    meta["worker"] = cell * env_workers + int(meta.get("worker", 0))
                    client.push_segment({k: np.asarray(v) for k, v in seg.items()}, meta=meta)
        except ServiceStopped:
            return  # queue closed under us, or the learner finished (410)
        except BaseException as e:  # surfaced by the control loop
            pusher_errors.append(e)
            stop_event.set()

    HUB.register("dcn.client", client.metrics)
    HUB.register("sebulba.traj_queue", local_queue.metrics)
    HUB.register("sebulba.broadcast", broadcast.metrics)
    SPANS.roll_window()
    arm_preemption(cfg)
    poll_interval = float(pod.get("poll_interval_s", 0.5))
    last_shard = -1
    reason = "done"
    pusher = threading.Thread(target=_pusher, name="dcn.pusher", daemon=True)
    try:
        for eng in engines:
            eng.start()
        supervisor.start()
        pusher.start()
        while True:
            resp = client.poll(
                applied, latched=PREEMPTION_GUARD.requested(), hub=HUB.collect()
            )
            if resp is not None:
                if int(resp.get("version", applied)) > applied:
                    fresh = client.fetch_params(applied)
                    if fresh is not None:
                        host_params, version = fresh
                        broadcast.publish(host_params, version=version)
                        applied = version
                # replay EVERY announced step, not just the latest: the
                # learner's async commit manager can announce two saves
                # between our polls, and each one's rank-0 commit is
                # waiting on our shard
                announced = [int(s) for s in resp.get("commit_steps", [])]
                if not announced and int(resp.get("commit_step", -1)) >= 0:
                    announced = [int(resp["commit_step"])]
                for commit_step in sorted(announced):
                    if commit_step <= last_shard:
                        continue
                    step_dir = ckpt_root / step_dir_name(commit_step)
                    step_dir.mkdir(parents=True, exist_ok=True)
                    write_shard(
                        step_dir, rank,
                        {
                            "pod_rank": rank,
                            "policy_step": commit_step,
                            "policy_version": int(applied),
                            "key": np.asarray(jax.device_get(key)),  # graftlint: disable=prng-key-reuse
                        },
                    )
                    last_shard = commit_step
                if resp.get("done"):
                    break
            if pusher_errors:
                raise pusher_errors[0]
            for eng in engines:
                if eng.error is not None:
                    raise eng.error
            supervisor.check()
            time.sleep(poll_interval)
    except BaseException as e:
        reason = f"{type(e).__name__}: {e}"
        raise
    finally:
        HUB.unregister("dcn.client")
        HUB.unregister("sebulba.traj_queue")
        HUB.unregister("sebulba.broadcast")
        shutdown(stop_event, local_queue, obs_queue, engines, supervisor)
        pusher.join(timeout=5.0)
        client.goodbye(reason)
