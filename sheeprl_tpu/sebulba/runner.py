"""Shared Sebulba driver scaffolding (one copy for ppo.py and sac.py):
queue sizing, the env-worker fleet builder, the learner's segment-drain
loop, teardown, and the run-stats assembly."""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from sheeprl_tpu.sebulba.actor import EnvWorker, WorkerSupervisor
from sheeprl_tpu.sebulba.queues import ObsQueue, ServiceStopped, TrajQueue
from sheeprl_tpu.telemetry.spans import span
from sheeprl_tpu.utils.env import make_env, vectorize


class DrainPreempted(Exception):
    """The SIGTERM/SIGINT preemption latch fired while the learner was
    blocked on the trajectory queue.  The drivers catch this, run a final
    SYNCHRONOUS committed save, and exit cleanly — a preempted split run
    must not sit out the (up to 300 s) queue timeout eating into the
    preemption grace window, nor die mid-wait with its progress
    uncommitted."""


class StatsSink:
    """Thread-safe episode-stats funnel (workers push, the learner drains
    into the metric aggregator at log time).  BOUNDED: with
    ``metric.log_level=0`` nothing ever drains, and short-episode fused
    actors can finish millions of episodes per minute — the ring keeps the
    newest ``maxlen`` completions instead of growing for the run's life."""

    def __init__(self, maxlen: int = 65536) -> None:
        from collections import deque

        self._lock = threading.Lock()
        self._items: Any = deque(maxlen=maxlen)

    def __call__(self, items) -> None:
        with self._lock:
            self._items.extend(items)

    def drain(self) -> List[Tuple[float, int]]:
        with self._lock:
            out = list(self._items)
            self._items.clear()
            return out


def clamp_queue_slots(topo_cfg: Dict[str, Any], n_producers: int) -> int:
    """The trajectory ring must hold at least one segment per producer:
    the learner pops ``n_producers`` per update, so a smaller ring can
    NEVER satisfy it (producers block, the learner starves)."""
    slots = int(topo_cfg.get("traj_queue_slots", 4))
    if slots < n_producers:
        import warnings

        warnings.warn(
            f"topology.traj_queue_slots={slots} < {n_producers} producers: "
            "raising the ring to one segment per producer",
            RuntimeWarning,
        )
        slots = n_producers
    return slots


def build_worker_fleet(
    cfg: Any,
    topo_cfg: Dict[str, Any],
    *,
    protocol: Any,
    obs_queue: ObsQueue,
    traj_queue: TrajQueue,
    segment_steps: int,
    num_workers: int,
    envs_per_worker: int,
    log_dir: str,
    stop_event: threading.Event,
    stats_sink: Callable,
    env_offset: int = 0,
) -> WorkerSupervisor:
    """The env-worker fleet both drivers spawn: worker ``i`` owns env slice
    ``[i*envs_per_worker, (i+1)*envs_per_worker)`` built through the
    standard ``make_env``/``vectorize`` machinery; a respawn (bumped
    generation) reseeds the slice so the fresh worker's streams diverge
    from the deposed one's.

    ``env_offset`` shifts the whole fleet's slice within a LARGER global
    env space: a pod actor cell owns ``[offset, offset + num_workers *
    envs_per_worker)`` of the pod-wide ``env.num_envs``, so seeds and
    ``vector_env_idx`` stay globally unique across cells."""

    def spawn(worker_id: int, generation: int) -> EnvWorker:
        base = env_offset + worker_id * envs_per_worker
        seed = cfg.seed + base + 100003 * generation

        def env_builder(_seed=seed, _base=base):
            return vectorize(
                cfg,
                [
                    make_env(cfg, _seed + j, 0, run_name=log_dir, vector_env_idx=_base + j)
                    for j in range(envs_per_worker)
                ],
            )

        return EnvWorker(
            worker_id, env_builder, protocol, obs_queue, traj_queue,
            segment_steps, seed,
            timeout_s=float(topo_cfg.get("queue_timeout_s", 300.0)),
            stop_event=stop_event, stats_sink=stats_sink, generation=generation,
        )

    return WorkerSupervisor(
        spawn, num_workers,
        deadline_s=float(topo_cfg.get("worker_deadline_s", 120.0)),
        max_restarts=int(topo_cfg.get("max_worker_restarts", 3)),
    )


def drain_segments(
    traj_queue: TrajQueue,
    n: int,
    engines: List[Any],
    supervisor: Optional[WorkerSupervisor],
    preempted: Optional[Callable[[], bool]] = None,
) -> List[Tuple[Dict[str, Any], Dict[str, Any]]]:
    """Pop ``n`` segments for one learner update, surfacing actor-engine
    failures and driving worker respawns while waiting — bounded by the
    queue's overall ``timeout_s`` so a wedged fused actor (which has no
    supervisor) fails the run loudly instead of hanging it.

    ``preempted`` (the drivers pass the checkpoint manager's rank-agreed
    latch) is polled between queue waits: a latched SIGTERM raises
    :class:`DrainPreempted` within one short wait (≤5 s) so the driver can
    depose the workers and exit through its final committed save."""
    deadline = time.monotonic() + traj_queue.timeout_s
    # the learner's queue wait is ITS OWN phase (telemetry/spans.py): time
    # spent here is actor starvation, not rollout work — the queue.wait
    # fraction of the phase breakdown is what traj_queue_slots tuning reads
    with span("queue.wait"):
        while True:
            if preempted is not None and preempted():
                raise DrainPreempted()
            try:
                return traj_queue.get_many(n, timeout_s=5.0)
            except TimeoutError:
                for eng in engines:
                    if eng.error is not None:
                        raise eng.error
                if supervisor is not None:
                    supervisor.check()
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"trajectory queue produced < {n} segments in "
                        f"{traj_queue.timeout_s}s — actors wedged?"
                    )


def arm_preemption(cfg: Any) -> None:
    """Install the SIGTERM/SIGINT latch BEFORE the fleet starts: the
    cadence poll (``should_save``) only runs after a full drain+update, and
    a signal landing during the first (or any) queue wait must still be
    caught — :func:`drain_preemptible` polls the latch for the drivers."""
    from sheeprl_tpu.checkpoint import PREEMPTION_GUARD

    if cfg.checkpoint.get("save_on_preemption", True):
        PREEMPTION_GUARD.install()


def drain_preemptible(
    traj_queue: TrajQueue,
    n: int,
    engines: List[Any],
    supervisor: Optional[WorkerSupervisor],
    *,
    ckpt_mgr: Any,
    fabric: Any,
    policy_step: int,
    save_checkpoint: Callable[[], None],
) -> Optional[List[Tuple[Dict[str, Any], Dict[str, Any]]]]:
    """:func:`drain_segments` + the shared preemption exit (one copy for
    both drivers): a latch fired mid-wait runs the driver's final
    SYNCHRONOUS committed save (``ckpt_mgr.preempted`` forces the sync
    path) and returns ``None`` — the caller breaks out of its round loop
    and the normal teardown deposes the workers."""
    try:
        return drain_segments(
            traj_queue, n, engines, supervisor, preempted=lambda: ckpt_mgr.preempted
        )
    except DrainPreempted:
        fabric.print(
            f"Preemption latched mid-drain: final committed save at "
            f"step {policy_step}, exiting"
        )
        save_checkpoint()
        return None


def shutdown(
    stop_event: threading.Event,
    traj_queue: TrajQueue,
    obs_queue: Optional[ObsQueue],
    engines: List[Any],
    supervisor: Optional[WorkerSupervisor],
    join_timeout_s: float = 10.0,
) -> None:
    """Teardown in dependency order: stop flags → queues closed (pending
    inference requests failed so blocked workers unblock) → engines
    stopped → workers deposed and joined → engines joined."""
    stop_event.set()
    traj_queue.close()
    if obs_queue is not None:
        for req in obs_queue.close():
            req.fail(ServiceStopped("sebulba run finished"))
    for eng in engines:
        if hasattr(eng, "stop"):
            eng.stop()
    if supervisor is not None:
        supervisor.stop()
    for eng in engines:
        eng.join(join_timeout_s)


def collect_run_stats(
    *,
    updates: int,
    env_steps: int,
    engines: List[Any],
    traj_queue: TrajQueue,
    broadcast: Any,
    traj_staleness_max: int,
    supervisor: Optional[WorkerSupervisor],
) -> Dict[str, Any]:
    """What ``run_sebulba`` returns: the run's counters, which
    ``tests/test_algos/test_sebulba.py`` holds the topology to (nothing
    torn or lost, staleness bounded, one executable per actor shape)."""
    return {
        "updates": int(updates),
        "env_steps": int(env_steps),
        "actor_idle_frac": float(np.mean([eng.actor_idle_frac() for eng in engines])),
        "queue_depth_frac": float(traj_queue.metrics()["Sebulba/queue_depth_frac"]),
        "param_staleness_max": int(broadcast.staleness_max),
        "traj_staleness_max": int(traj_staleness_max),
        "actor_cache_sizes": [eng.cache_sizes() for eng in engines],
        "worker_restarts": supervisor.restarts if supervisor is not None else 0,
        "torn_rejected": traj_queue.torn_rejected,
    }
