"""Sebulba dataflow queues: the shared observation queue and the
device-resident trajectory queue.

* :class:`ObsQueue` is the actor-side admission path: env workers submit
  fixed-shape observation *blocks* (one per worker per step) and the actor
  dispatcher coalesces the head of the queue into one padded inference
  batch — exactly the :mod:`sheeprl_tpu.serve.batcher` continuous-batching
  pattern (bounded FIFO, max-batch/max-wait anchored to the oldest block),
  re-instantiated for rollout inference instead of HTTP requests.

* :class:`TrajQueue` is the learner-side trajectory ring: a bounded queue
  of rollout segments whose payloads live ON the learner sub-mesh (staged
  with ``learner_fabric.shard_batch`` along the env axis where it divides,
  replicated otherwise — the ``data/device_replay.py`` placement, one
  window at a time).  Capacity bounds the HBM the queue may pin; a full
  queue **blocks producers** (backpressure — trajectories are never
  dropped), and depth is tracked (``Sebulba/queue_depth_frac``) so a run
  reports how full the pipe runs.

Both queues carry the ``sebulba.traj_queue`` / ``sebulba.env_worker``
fault sites' consequences: a ``truncate`` fault at the trajectory queue
models a torn segment — :meth:`TrajQueue.put` **rejects** it (shape
validation against the segment contract) instead of feeding the learner a
short rollout, so chaos drills can assert "no torn trajectories" as a
hard property of the dataflow.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from sheeprl_tpu.resilience.faults import fault_rows
from sheeprl_tpu.serve.batcher import AdmissionQueue, QueueFull, ServiceStopped  # noqa: F401


class TornTrajectory(ValueError):
    """A segment whose leading (time) axis does not match the queue's
    contract — e.g. a ``sebulba.traj_queue`` truncate fault."""


class ObsBlock:
    """One env worker's observation block awaiting actor inference.

    The dispatcher resolves it with the per-row policy outputs; the worker
    blocks in :meth:`wait`.  Mirrors ``serve.batcher._Request`` (enqueued
    timestamp drives the coalescer's max-wait anchor; ``cancelled`` lets a
    deposed worker's block be skipped instead of burning batch rows).
    """

    __slots__ = ("worker_id", "obs", "rows", "enqueued", "event", "result", "error", "cancelled")

    def __init__(self, worker_id: int, obs: Dict[str, np.ndarray], rows: int):
        self.worker_id = int(worker_id)
        self.obs = obs
        self.rows = int(rows)
        self.enqueued = time.perf_counter()
        self.event = threading.Event()
        self.result: Optional[Dict[str, np.ndarray]] = None
        self.error: Optional[BaseException] = None
        self.cancelled = False

    def wait(self, timeout: Optional[float] = None) -> Dict[str, np.ndarray]:
        if not self.event.wait(timeout):
            self.cancelled = True
            raise TimeoutError("actor inference request timed out")
        if self.error is not None:
            raise self.error
        return self.result

    def resolve(self, result: Dict[str, np.ndarray]) -> None:
        self.result = result
        self.event.set()

    def fail(self, error: BaseException) -> None:
        self.error = error
        self.event.set()


class ObsQueue(AdmissionQueue):
    """The shared observation queue (bounded FIFO + coalescing pop).

    Capacity defaults to the worker count: every worker can have at most
    one block in flight, so the queue can never grow past one round."""

    def __init__(self, max_pending: int):
        super().__init__(max_pending=max_pending)


class _DepthMeter:
    """Time-weighted queue-depth integral: ``frac()`` is the average
    fraction of capacity occupied since :meth:`start` (updated at every
    put/get transition, so idle stretches count at their true depth)."""

    def __init__(self, capacity: int):
        self.capacity = max(1, int(capacity))
        self._depth = 0
        self._t0 = time.perf_counter()
        self._last = self._t0
        self._area = 0.0
        self._max = 0

    def start(self) -> None:
        self._t0 = time.perf_counter()
        self._last = self._t0
        self._area = 0.0

    def move(self, delta: int) -> None:
        now = time.perf_counter()
        self._area += self._depth * (now - self._last)
        self._last = now
        self._depth += delta
        self._max = max(self._max, self._depth)

    def frac(self) -> float:
        now = time.perf_counter()
        area = self._area + self._depth * (now - self._last)
        return area / (self.capacity * max(now - self._t0, 1e-9))

    @property
    def max_depth(self) -> int:
        return self._max


class TrajQueue:
    """Bounded device-resident trajectory queue on the learner sub-mesh.

    ``put`` stages a rollout segment (dict of ``(T, B, *feat)`` arrays plus
    optional ``(B, *feat)`` bootstrap leaves) onto the learner mesh and
    appends it; while ``capacity`` segments are pending the producer
    **blocks** (backpressure).  ``get_many(n)`` pops the ``n`` oldest
    segments for one learner update.  ``stage=False`` keeps payloads on the
    host (the SAC driver appends them into its own ``DeviceReplay`` HBM
    ring — the device-resident store is the ring itself, the queue adds
    only ordering + backpressure).

    Segment metadata travels alongside the payload: the param version the
    segment was collected with (staleness accounting), its worker id, and
    its env-step count (throughput accounting).

    In the pod topology the same queue (and the same contract) sits at
    BOTH ends of the DCN: each actor cell buffers its workers' segments in
    a host-side queue (``stage=False``) drained by the transport pusher,
    and the learner front feeds its staged queue from CRC-verified HTTP
    intake (``sebulba/transport.py``) — torn segments are rejected at the
    wire with the exact :class:`TornTrajectory` semantics used in-process.
    """

    def __init__(
        self,
        capacity: int,
        rollout_steps: int,
        learner_fabric: Any = None,
        *,
        stage: bool = True,
        bootstrap_keys: Tuple[str, ...] = (),
        timeout_s: float = 300.0,
    ):
        self.capacity = max(1, int(capacity))
        self.rollout_steps = int(rollout_steps)
        self.learner_fabric = learner_fabric
        self.stage = bool(stage) and learner_fabric is not None
        self.bootstrap_keys = tuple(bootstrap_keys)
        self.timeout_s = float(timeout_s)
        self._items: List[Tuple[Dict[str, Any], Dict[str, Any]]] = []
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._closed = False
        self._meter = _DepthMeter(self.capacity)
        self._meter.start()
        self.torn_rejected = 0
        self.put_wait_s = 0.0
        self.get_wait_s = 0.0
        self.total_put = 0

    # -- staging --------------------------------------------------------------
    def _stage(self, segment: Dict[str, Any]) -> Dict[str, Any]:
        """Land the segment on the learner mesh: env axis (axis 1 of the
        ``(T, B, ...)`` rollout leaves, axis 0 of bootstrap leaves) sharded
        over the learner ``data`` axis when it divides, replicated
        otherwise — ``device_replay``'s placement rule."""
        fab = self.learner_fabric
        n = int(fab.mesh.shape[fab.data_axis])
        out = {}
        for k, v in segment.items():
            axis = 0 if k in self.bootstrap_keys else 1
            rows = int(np.shape(v)[axis]) if np.ndim(v) > axis else 0
            if rows and rows % n == 0:
                # host leaves: one explicit H2D onto the sharded layout;
                # actor-device leaves (fused jax rollout shards): a pure
                # D2D reshard — legal under the H2D transfer guard
                out[k] = fab.shard_batch(v, axis=axis)
            else:
                out[k] = fab.replicate(v if hasattr(v, "devices") else np.asarray(v))
        return out

    def _validate(self, segment: Dict[str, Any]) -> None:
        for k, v in segment.items():
            if k in self.bootstrap_keys:
                continue
            t = int(np.shape(v)[0]) if np.ndim(v) else -1
            if t != self.rollout_steps:
                raise TornTrajectory(
                    f"segment leaf '{k}' has {t} rows, expected "
                    f"rollout_steps={self.rollout_steps} — torn trajectory "
                    "rejected (never enqueued)"
                )

    # -- producer -------------------------------------------------------------
    def put(
        self,
        segment: Dict[str, Any],
        meta: Optional[Dict[str, Any]] = None,
        abort: Optional[Any] = None,
    ) -> None:
        """Stage + append one segment; blocks while the ring is full.

        ``abort`` (a callable) is evaluated under the queue lock on every
        backpressure wait slice AND immediately before the append: a
        producer whose ``abort()`` turns true (a deposed env worker) backs
        out with :class:`ServiceStopped` instead of delivering a stale
        segment — the generation fence that keeps a respawn from
        duplicating trajectories.

        The ``sebulba.traj_queue`` fault site acts here: ``latency``/
        ``hang`` delay the producer, ``raise`` fails it (the worker
        respawn path), ``truncate`` tears the segment — which the shape
        validation then rejects with :class:`TornTrajectory` so a torn
        segment can never reach the learner."""
        rollout_leaves = {k: v for k, v in segment.items() if k not in self.bootstrap_keys}
        rollout_leaves = fault_rows("sebulba.traj_queue", rollout_leaves)
        segment = {**segment, **rollout_leaves}
        try:
            self._validate(segment)
        except TornTrajectory:
            with self._lock:
                self.torn_rejected += 1
            raise
        staged = self._stage(segment) if self.stage else segment
        deadline = time.monotonic() + self.timeout_s
        t0 = time.perf_counter()
        with self._lock:
            while len(self._items) >= self.capacity and not self._closed:
                if abort is not None and abort():
                    raise ServiceStopped("producer deposed while waiting")
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise QueueFull(
                        f"trajectory queue full ({self.capacity} segments) "
                        f"for {self.timeout_s}s — learner wedged?"
                    )
                self._not_full.wait(min(remaining, 0.2))
            if self._closed:
                raise ServiceStopped("trajectory queue closed")
            if abort is not None and abort():
                raise ServiceStopped("producer deposed while waiting")
            self.put_wait_s += time.perf_counter() - t0
            self._items.append((staged, dict(meta or {})))
            self.total_put += 1
            self._meter.move(+1)
            self._not_empty.notify_all()

    # -- consumer -------------------------------------------------------------
    def get_many(
        self, n: int, timeout_s: Optional[float] = None
    ) -> List[Tuple[Dict[str, Any], Dict[str, Any]]]:
        """Pop the ``n`` oldest segments (blocking).  Returns fewer only
        when the queue is closed and drained."""
        effective = self.timeout_s if timeout_s is None else float(timeout_s)
        deadline = time.monotonic() + effective
        t0 = time.perf_counter()
        with self._lock:
            while len(self._items) < n and not self._closed:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"trajectory queue: {len(self._items)}/{n} segments "
                        f"after {effective}s — actors wedged?"
                    )
                self._not_empty.wait(min(remaining, 0.2))
            self.get_wait_s += time.perf_counter() - t0
            take = min(n, len(self._items))
            # per-pop queue-depth sample for the flight recorder (bounded
            # ring, learner-update cadence) — postmortems show whether the
            # queue was starved or backed up when the run died
            from sheeprl_tpu.telemetry.recorder import RECORDER

            RECORDER.record(
                "queue.depth", depth=len(self._items), frac=round(self._meter.frac(), 4)
            )
            out, self._items = self._items[:take], self._items[take:]
            self._meter.move(-take)
            self._not_full.notify_all()
            return out

    def qsize(self) -> int:
        with self._lock:
            return len(self._items)

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._not_empty.notify_all()
            self._not_full.notify_all()

    @property
    def closed(self) -> bool:
        return self._closed

    # -- observability --------------------------------------------------------
    def metrics(self) -> Dict[str, float]:
        with self._lock:
            return {
                "Sebulba/queue_depth": float(len(self._items)),
                "Sebulba/queue_depth_frac": float(self._meter.frac()),
                "Sebulba/queue_depth_max": float(self._meter.max_depth),
                "Sebulba/queue_put_wait_s": float(self.put_wait_s),
                "Sebulba/queue_get_wait_s": float(self.get_wait_s),
                "Sebulba/queue_torn_rejected": float(self.torn_rejected),
                # accepted-segment count: the pod zero-drop gate compares
                # this against the transport's pushed/accepted counters
                "Sebulba/queue_total_put": float(self.total_put),
            }
