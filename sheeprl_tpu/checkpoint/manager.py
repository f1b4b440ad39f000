"""Checkpoint orchestration: cadence, async saves, commit, retention, resume.

One :class:`CheckpointManager` per training run, created by the fabric the
first time a train loop binds its ``log_dir`` (``fabric.checkpoint_manager``).
The manager owns:

* the **cadence decision** (``checkpoint.every`` policy steps, the final
  ``save_last`` save, and any pending preemption — see ``preemption.py``);
* the **save path**: snapshot on the caller thread, shard write + commit on
  the :class:`~sheeprl_tpu.checkpoint.writer.AsyncCheckpointWriter` thread
  (``checkpoint.async_save=True``, the default) or inline + barrier for the
  synchronous cases (preemption finals, ``async_save=False``);
* **retention**: keep-last-N (``checkpoint.keep_last``) plus keep-every-K
  policy steps (``checkpoint.keep_every``), applied by rank 0 after each
  commit;
* **resume discovery**: :func:`resolve_auto_resume` scans every run under
  the experiment root for the newest committed snapshot
  (``checkpoint.resume_from=auto``).

Rank protocol: every rank saves its OWN shard (its replay-buffer state is
rank-local); rank 0 additionally waits for all shards and writes the
manifest + ``COMMIT`` marker (see ``protocol.py``).
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Any, Dict, Optional, Union

from sheeprl_tpu.checkpoint.preemption import PREEMPTION_GUARD
from sheeprl_tpu.checkpoint.protocol import (
    gc_checkpoints,
    latest_checkpoint,
    probe_shared_root,
    step_dir_name,
    write_commit,
    write_shard,
    write_shared_root_probe,
)
from sheeprl_tpu.checkpoint.serialize import snapshot_tree, to_host_tree
from sheeprl_tpu.checkpoint.writer import AsyncCheckpointWriter
from sheeprl_tpu.utils.profiler import CHECKPOINT_MONITOR


class CheckpointManager:
    def __init__(self, fabric: Any, cfg: Any, log_dir: Union[str, os.PathLike]):
        ckpt_cfg = cfg.checkpoint if "checkpoint" in cfg else {}
        self.fabric = fabric
        self.every = int(ckpt_cfg.get("every", 0) or 0)
        self.save_last = bool(ckpt_cfg.get("save_last", True))
        self.keep_last = ckpt_cfg.get("keep_last", 5)
        self.keep_every = ckpt_cfg.get("keep_every")
        self.async_save = bool(ckpt_cfg.get("async_save", True))
        self.queue_size = int(ckpt_cfg.get("queue_size", 2) or 2)
        self.commit_timeout_s = float(ckpt_cfg.get("commit_timeout_s", 300.0))
        self.io_retries = int(ckpt_cfg.get("io_retries", 3) or 1)
        self.io_retry_base_s = float(ckpt_cfg.get("io_retry_base_s", 0.5))
        self.hang_warn_s = float(ckpt_cfg.get("hang_warn_s", 120.0) or 0)
        self.preemption_poll_every = int(ckpt_cfg.get("preemption_poll_every", 10) or 10)
        self.save_on_preemption = bool(ckpt_cfg.get("save_on_preemption", True))
        self.root = Path(log_dir) / "checkpoint"
        self._writer: Optional[AsyncCheckpointWriter] = None
        self._guard = PREEMPTION_GUARD
        self._finalized = False
        self._iter = 0
        self._agreed_preempt = False
        # lockstep=False (the pod topology): ranks do NOT call should_save /
        # save in the same iteration, so the collective preemption poll and
        # the post-save barrier are off — agreement arrives over the pod
        # control plane via force_preempt() instead
        self.lockstep = True
        self._probed_shared_root = False
        if fabric.num_processes > 1 and fabric.global_rank == 0:
            # rank 0 drops the shared-root probe marker NOW so rank >0's
            # first save can fail fast when checkpoint.root is host-local
            try:
                write_shared_root_probe(self.root)
            except OSError:
                pass  # surfaced properly by the first real save

    # -- cadence -------------------------------------------------------------
    @property
    def preempted(self) -> bool:
        """Rank-agreed preemption flag.

        Single-process: the local SIGTERM/SIGINT latch directly.
        Multi-process: the flag only flips after :meth:`should_save` has
        exchanged latches across ranks — a signal usually reaches ranks at
        different loop iterations, and a single rank unilaterally entering
        the final save would leave the commit waiting on shards the other
        ranks never write (and desequence the fabric's collectives).
        """
        if self._agreed_preempt:
            return True
        if (self.fabric.num_processes <= 1 or not self.lockstep) and self._guard.requested():
            self._agreed_preempt = True
        return self._agreed_preempt

    def force_preempt(self) -> None:
        """Adopt a preemption decided OUTSIDE the collective poll — the pod
        control plane (an actor cell's latch surfaced by its ``/poll``)
        calls this so the learner enters the same final committed save the
        in-process latch would trigger."""
        self._agreed_preempt = True

    def _poll_preemption(self) -> bool:
        """Latch preemption IN AGREEMENT across ranks: every
        ``checkpoint.preemption_poll_every`` loop iterations all ranks
        all-gather their local latch (the coupled loops call
        :meth:`should_save` in lockstep, so the collective lines up) and
        every rank adopts ``any(latches)`` — they then enter the same final
        synchronous save at the same step, and the commit completes."""
        if self._agreed_preempt:
            return True
        if self.fabric.num_processes <= 1 or not self.lockstep:
            return self.preempted
        if self._iter % self.preemption_poll_every == 0:
            flags = self.fabric.all_gather_object(bool(self._guard.requested()))
            self._agreed_preempt = any(flags)
        return self._agreed_preempt

    def should_save(self, policy_step: int, last_checkpoint: int, final: bool = False) -> bool:
        """The one cadence rule every loop shares: the ``checkpoint.every``
        policy-step interval, the ``save_last`` final save, or a pending
        (rank-agreed) preemption — which must snapshot NOW regardless of
        cadence.

        Polling is also what ARMS the SIGTERM/SIGINT latch (idempotent):
        only loops that read the latch install the handler, so surfaces that
        never poll (dedicated lockstep topologies, the evaluation CLI) keep
        the default one-signal-kills disposition instead of silently
        swallowing the preemption grace window."""
        if self.save_on_preemption:
            self._guard.install()
        self._iter += 1
        if self._poll_preemption():
            return True
        if self.every > 0 and policy_step - last_checkpoint >= self.every:
            return True
        return final and self.save_last

    # -- saving --------------------------------------------------------------
    def step_dir(self, step: int) -> Path:
        return self.root / step_dir_name(step)

    def save(self, step: int, state: Dict[str, Any], sync: Optional[bool] = None) -> Path:
        """Checkpoint ``state`` as this rank's shard of snapshot ``step``.

        The snapshot (device-side copies + host memcpys) happens HERE, on
        the caller thread, so the caller may keep mutating buffers and
        donating params immediately after this returns.  Everything slow —
        fence, ``device_get``, pickle, fsync'd writes, commit, retention —
        runs on the writer thread unless ``sync`` (preemption finals,
        ``checkpoint.async_save=False``).
        """
        if sync is None:
            sync = not self.async_save or self.preempted
        rank = self.fabric.global_rank
        world = self.fabric.num_processes
        step_dir = self.step_dir(step)
        step_dir.mkdir(parents=True, exist_ok=True)
        snap = snapshot_tree(state)

        def job() -> int:
            from sheeprl_tpu.utils.utils import device_sync

            if world > 1 and rank > 0 and not self._probed_shared_root:
                # fail fast with the shared-storage error instead of rank
                # 0's bare wait_for_shards timeout minutes later
                probe_shared_root(self.root, rank, timeout_s=60.0)
                self._probed_shared_root = True
            # completion fence before the host fetch
            device_sync(snap)
            meta = write_shard(step_dir, rank, to_host_tree(snap))
            if rank == 0:
                committed = write_commit(
                    step_dir, step=step, world=world, timeout_s=self.commit_timeout_s
                )
                if committed:
                    gc_checkpoints(self.root, self.keep_last, self.keep_every)
            return meta["bytes"]

        if sync:
            # a concurrent writer-thread GC/commit must not interleave with
            # the inline job on the same rank: drain first
            if self._writer is not None:
                self._writer.flush()
            from sheeprl_tpu.checkpoint.writer import run_with_io_retry

            t0 = time.perf_counter()
            # same transient-IO tolerance as the async writer: a preemption
            # final save racing a flaky disk should not lose the run
            nbytes = run_with_io_retry(job, self.io_retries, self.io_retry_base_s)
            CHECKPOINT_MONITOR.record_save(
                seconds=time.perf_counter() - t0, nbytes=nbytes, asynchronous=False
            )
            # all ranks leave the save together so no rank races ahead into
            # teardown while rank 0 still waits on its shards (lockstep
            # loops only: pod cells are not in the same iteration, and the
            # commit wait itself is the learner's ordering fence)
            if self.lockstep:
                self.fabric.barrier()
        else:
            if self._writer is None:
                self._writer = AsyncCheckpointWriter(
                    queue_size=self.queue_size,
                    io_retries=self.io_retries,
                    io_retry_base_s=self.io_retry_base_s,
                    hang_warn_s=self.hang_warn_s,
                )
            self._writer.submit(job)
        return step_dir

    # -- resume --------------------------------------------------------------
    def latest(self) -> Optional[Path]:
        return latest_checkpoint(self.root)

    def flush(self) -> None:
        """Drain outstanding async saves WITHOUT finalizing (the rollback
        path needs pending commits on disk, then keeps checkpointing)."""
        if self._writer is not None:
            self._writer.flush()

    # -- lifecycle -----------------------------------------------------------
    def finalize(self, timeout_s: Optional[float] = 300.0) -> None:
        """Drain outstanding async saves (idempotent; call before teardown)."""
        if self._finalized:
            return
        self._finalized = True
        if self._writer is not None:
            self._writer.close(timeout_s)
            self._writer = None


def resolve_auto_resume(
    base: Union[str, os.PathLike],
    root_dir: Union[str, os.PathLike],
    exclude: Any = (),
) -> Optional[Path]:
    """``checkpoint.resume_from=auto``: newest committed snapshot across
    every run/version under ``<base>/<root_dir>`` (run names are usually
    timestamped, so a relaunch gets a FRESH run dir and must look across
    its siblings).  "Newest" is by commit time, not step: step counters
    from unrelated restarts of the same experiment are not comparable."""
    import glob

    from sheeprl_tpu.checkpoint.protocol import checkpoint_step

    root = os.path.join(os.fspath(base), os.fspath(root_dir))
    best: Optional[Path] = None
    best_mtime = -1.0
    for ckpt_root in glob.glob(os.path.join(root, "*", "version_*", "checkpoint")):
        for step_dir in map(Path, glob.glob(os.path.join(ckpt_root, "step_*"))):
            if checkpoint_step(step_dir) < 0:
                continue  # quarantined (step_*.corrupt) snapshots are out
            if step_dir in exclude:
                continue  # known-damaged but un-renameable (read-only store)
            commit = step_dir / "COMMIT"
            try:
                mtime = commit.stat().st_mtime
            except OSError:
                continue  # uncommitted (torn) snapshots are never eligible
            if mtime > best_mtime:
                best, best_mtime = step_dir, mtime
    return best
