"""Checkpoint callback.

Role parity with the reference's ``CheckpointCallback``
(reference: sheeprl/utils/callback.py:14-148): algorithms fire
``fabric.call("on_checkpoint_coupled", ...)`` (or ``_player``/``_trainer`` in
the decoupled topology) and the callback attaches replay-buffer state, applies
the buffer-consistency trick, saves, and prunes old checkpoints.

Buffer-consistency trick: the environment state is not checkpointed, so on
resume the step at the write head must not be treated as a continuation — the
last stored step is temporarily marked truncated/done for the save and
restored afterwards (reference: sheeprl/utils/callback.py:87-142).
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Dict, Optional

import numpy as np

from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, EpisodeBuffer, ReplayBuffer
from sheeprl_tpu.telemetry.spans import span
from sheeprl_tpu.utils.checkpoint import prune_checkpoints


class CheckpointCallback:
    def __init__(self, keep_last: Optional[int] = 5):
        self.keep_last = keep_last

    # -- hooks -------------------------------------------------------------
    def on_checkpoint_coupled(
        self,
        fabric: Any,
        ckpt_path: str,
        state: Dict[str, Any],
        replay_buffer: Any = None,
    ) -> None:
        # ckpt.save: the part of a checkpoint that holds the calling loop (the
        # buffer's state, the on-device copies, the host memcpys); the writer
        # thread's ckpt.snapshot takes this span as its parent
        with span("ckpt.save", phase=False):
            if replay_buffer is not None:
                with _consistent_tail(replay_buffer):
                    state = dict(state)
                    state["rb"] = _buffer_state(replay_buffer)
                    # with an async manager the state SNAPSHOT (host memcpys)
                    # happens inside save() on this thread, i.e. still under the
                    # tail patch — only serialization/IO runs in the background
                    self._save(fabric, ckpt_path, state)
            else:
                self._save(fabric, ckpt_path, state)

    def on_checkpoint_player(self, fabric: Any, ckpt_path: str, state: Dict[str, Any], replay_buffer: Any = None) -> None:
        self.on_checkpoint_coupled(fabric, ckpt_path, state, replay_buffer)

    def on_checkpoint_trainer(self, fabric: Any, ckpt_path: str, state: Dict[str, Any]) -> None:
        with span("ckpt.save", phase=False):
            self._save(fabric, ckpt_path, state)

    # -- save routing --------------------------------------------------------
    def _save(self, fabric: Any, ckpt_path: str, state: Dict[str, Any]) -> None:
        """Route through the run's CheckpointManager (async snapshots, commit
        protocol, retention — sheeprl_tpu/checkpoint) when the loop has bound
        one; otherwise the legacy single-file path + flat-file pruning."""
        manager = getattr(fabric, "checkpoint_manager", None)
        if manager is not None:
            manager.save(int(state.get("policy_step", 0)), state)
            return
        fabric.save(ckpt_path, state)
        if fabric.is_global_zero:
            prune_checkpoints(Path(ckpt_path).parent, self.keep_last)


def _buffer_state(rb: Any) -> Any:
    if isinstance(rb, (list, tuple)):
        return [b.state_dict() for b in rb]
    return rb.state_dict()


class _consistent_tail:
    """Temporarily force the last written step to look like an episode end."""

    def __init__(self, rb: Any):
        self.rbs = []
        for buf in rb if isinstance(rb, (list, tuple)) else [rb]:
            if isinstance(buf, EnvIndependentReplayBuffer):
                self.rbs.extend(buf.buffer)
            elif isinstance(buf, ReplayBuffer):
                self.rbs.append(buf)
            # EpisodeBuffer drops open episodes in state_dict already
        self._saved = []

    def __enter__(self) -> "_consistent_tail":
        for rb in self.rbs:
            patch = {}
            # Buffers that store an explicit next_obs per row (SAC/DroQ style)
            # need no tail patch: every row is self-contained, and forcing a
            # fake terminated=1 would permanently drop that row's bootstrap
            # after a buffer-checkpointed resume.
            if len(rb) == 0 or any(k.startswith("next_") for k in rb.keys()):
                self._saved.append(patch)
                continue
            tail = (rb._pos - 1) % rb.buffer_size
            # Only episode-boundary keys that mean "do not continue across the
            # checkpoint" are patched: truncated/dones. Never force
            # terminated=1 — that is a value-semantics (bootstrap-killing)
            # flag, not a storage-boundary one.
            for key in ("truncated", "dones"):
                if key in rb:
                    patch[key] = (tail, np.array(rb._buf[key][tail]))
                    rb._buf[key][tail] = np.ones_like(np.asarray(rb._buf[key][tail]))
            self._saved.append(patch)
        return self

    def __exit__(self, *exc: Any) -> bool:
        for rb, patch in zip(self.rbs, self._saved):
            for key, (tail, val) in patch.items():
                rb._buf[key][tail] = val
        return False
