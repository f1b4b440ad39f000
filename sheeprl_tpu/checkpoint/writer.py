"""Background checkpoint writer.

One daemon thread drains a BOUNDED queue of save jobs.  The split of work
between threads is the point of the design:

* **Caller thread** (the train loop): takes the point-in-time snapshot
  (``serialize.snapshot_tree`` — on-device copies dispatched async, host
  memcpys) and enqueues.  Cost: microseconds of dispatch + the host copy,
  never a device sync.
* **Writer thread**: fences the snapshot with ``utils.device_sync``,
  performs the blocking ``jax.device_get``, pickles, CRCs, and writes
  durably — all overlapped with the next update step on the main thread.

The queue is bounded (default 2 in-flight snapshots): if training
checkpoints faster than the disk drains, ``submit`` blocks — back-pressure
instead of unbounded host-memory growth from queued device copies.

Liveness (the resilience layer, docs/resilience.md):

* Transient IO errors (``OSError``) are retried with jittered exponential
  backoff (``checkpoint.io_retries`` attempts) BEFORE the job's exception
  is parked — an NFS blip no longer voids a snapshot.
* A failed job parks its exception and re-raises on the NEXT ``submit`` /
  ``flush`` so a dying disk cannot silently drop checkpoints for the rest
  of a run.
* A :class:`~sheeprl_tpu.resilience.retry.Watchdog` flags a job that has
  made no progress for ``hang_warn_s`` (``Resilience/watchdog_stalls`` + a
  warning) — the first visible symptom of a dead disk, minutes before any
  syscall would error.
* ``close()`` must return even when the worker is wedged mid-syscall on
  dead storage: the drain wait and the thread join are both bounded, and
  an un-joinable worker is ABANDONED with a logged warning (it is a daemon
  thread; interpreter shutdown does not wait for it).

Save timing/bytes are reported into ``utils.profiler.CHECKPOINT_MONITOR``
and surface as ``Checkpoint/*`` metrics through
``utils.metric.flush_metrics``.
"""

from __future__ import annotations

import queue
import threading
import time
import warnings
from typing import Any, Callable, Optional, Tuple

from sheeprl_tpu.telemetry.spans import SPANS
from sheeprl_tpu.utils.profiler import CHECKPOINT_MONITOR


def run_with_io_retry(job: Callable[[], Any], attempts: int, base_s: float) -> Any:
    """THE transient-IO retry policy for checkpoint writes — shared by the
    async writer and the manager's synchronous (preemption-final) path so
    the two can never diverge."""
    from sheeprl_tpu.resilience.retry import retry

    return retry(
        job,
        attempts=attempts,
        base_s=base_s,
        max_s=30.0,
        retry_on=(OSError,),
        site="checkpoint.write",
    )


class AsyncCheckpointWriter:
    """Single background thread executing checkpoint save jobs in order."""

    def __init__(
        self,
        queue_size: int = 2,
        name: str = "ckpt-writer",
        io_retries: int = 3,
        io_retry_base_s: float = 0.5,
        hang_warn_s: float = 120.0,
    ):
        # (job, the span open on the submitting thread), or None to stop
        self._queue: "queue.Queue[Optional[Tuple[Callable[[], Any], Any]]]" = queue.Queue(
            maxsize=max(1, int(queue_size))
        )
        self._error: Optional[BaseException] = None
        self._idle = threading.Event()
        self._idle.set()
        # pending counter incremented BEFORE the queue put: relying on
        # queue.unfinished_tasks alone leaves a window between idle.clear()
        # and put() where the worker, finishing the previous job, would see
        # zero unfinished tasks and re-set idle under a queued submit
        self._pending = 0
        self._pending_lock = threading.Lock()
        self._closed = False
        self._io_retries = max(1, int(io_retries))
        self._io_retry_base_s = float(io_retry_base_s)
        self._watchdog: Optional[Any] = None
        if hang_warn_s and hang_warn_s > 0:
            from sheeprl_tpu.resilience.retry import Watchdog

            self._watchdog = Watchdog(
                float(hang_warn_s),
                on_stall=lambda stalled: warnings.warn(
                    f"checkpoint writer job has made no progress for "
                    f"{stalled:.0f}s — storage may be wedged",
                    RuntimeWarning,
                ),
                name="ckpt-writer-watchdog",
            )
        self._thread = threading.Thread(target=self._loop, name=name, daemon=True)
        self._thread.start()

    # -- worker --------------------------------------------------------------
    def _run_job(self, job: Callable[[], Any]) -> Any:
        """One job, with jittered-backoff retry on transient IO errors —
        a blip must not park an exception and void the snapshot."""
        return run_with_io_retry(job, self._io_retries, self._io_retry_base_s)

    def _loop(self) -> None:
        while True:
            item = self._queue.get()
            if item is None:
                self._queue.task_done()
                return
            job, cause = item
            t0 = time.perf_counter()
            if self._watchdog is not None:
                self._watchdog.arm()
            try:
                # writer-thread span: snapshot cost shows up in the phase
                # breakdown as concurrent ckpt.snapshot time, distinct from
                # the learner's critical path (telemetry/spans.py); its
                # parent and iteration are those of the span that queued it
                # (the caller's ckpt.save)
                token = SPANS.push("ckpt.snapshot", cause=cause)
                try:
                    nbytes = self._run_job(job)
                finally:
                    SPANS.pop(token)
                CHECKPOINT_MONITOR.record_save(
                    seconds=time.perf_counter() - t0,
                    nbytes=int(nbytes or 0),
                    asynchronous=True,
                )
            except BaseException as e:  # parked, re-raised on next submit/flush
                self._error = e
                CHECKPOINT_MONITOR.record_error()
            finally:
                if self._watchdog is not None:
                    self._watchdog.disarm()
                self._queue.task_done()
                with self._pending_lock:
                    self._pending -= 1
                    if self._pending == 0:
                        self._idle.set()

    # -- API -----------------------------------------------------------------
    @property
    def in_flight(self) -> int:
        return self._queue.unfinished_tasks

    def _raise_pending(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError("async checkpoint save failed") from err

    def submit(self, job: Callable[[], Any]) -> None:
        """Enqueue a save job (a callable returning the bytes written).
        Blocks when the bounded queue is full (back-pressure)."""
        if self._closed:
            raise RuntimeError("AsyncCheckpointWriter is closed")
        self._raise_pending()
        with self._pending_lock:
            self._pending += 1
            self._idle.clear()
        self._queue.put((job, SPANS.current()))
        CHECKPOINT_MONITOR.record_depth(self.in_flight)

    def flush(self, timeout_s: Optional[float] = None) -> bool:
        """Wait until every queued job has finished.  Raises a parked writer
        error; returns False only on timeout."""
        done = self._idle.wait(timeout_s)
        self._raise_pending()
        return done

    def close(self, timeout_s: Optional[float] = 300.0) -> None:
        """Drain outstanding jobs and stop the thread (idempotent).  Must
        return within ~``timeout_s`` even when the worker is wedged on a
        dead disk: every wait below is bounded, the sentinel put uses a
        timeout too (a full bounded queue under a stuck worker would
        otherwise block forever), and an un-joinable worker is abandoned
        with a warning — it is a daemon thread, so interpreter shutdown
        does not hang on it."""
        if self._closed:
            return
        self._closed = True
        drained = self._idle.wait(timeout_s)
        # the wedged path's residual waits scale DOWN with a small timeout_s
        # (close(0.3) must not spend a fixed 5+5s on sentinel + join)
        grace = 5.0 if timeout_s is None else max(0.1, min(5.0, float(timeout_s)))
        try:
            self._queue.put(None, timeout=grace)
        except queue.Full:
            pass  # wedged worker + full queue: the join below gives up fast
        self._thread.join(timeout_s if drained else grace)
        if self._thread.is_alive():
            abandoned = max(self.in_flight, 1)
            try:
                warnings.warn(
                    f"checkpoint writer did not drain within "
                    f"{timeout_s if drained else grace}s; abandoning the daemon "
                    f"thread with ~{abandoned} job(s) wedged (likely dead "
                    "storage) — those snapshots stay uncommitted and are "
                    "invisible to resume",
                    RuntimeWarning,
                )
            except Exception:
                pass  # warning machinery can be torn down at interpreter exit
        if self._watchdog is not None:
            self._watchdog.close()
        self._raise_pending()
