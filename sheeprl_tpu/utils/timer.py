"""Named wall-clock timers.

Same role as the reference's ``timer`` ContextDecorator
(reference: sheeprl/utils/timer.py:16-83): train loops wrap the env-interaction
and train phases, and at log time derived steps-per-second throughputs are
computed then timers reset.

JAX note on attribution: dispatch is asynchronous, so by default a phase's
measured time is its HOST time — device compute dispatched in the train
phase that the host never waits for lands in whichever later phase first
blocks (on a single-stream host that is usually the env phase's next
device call).  ``metric.sync_timers=True`` (``timer.sync``) makes every
timed phase drain the device at entry and exit, so phase times are
attributable at the cost of losing host/device overlap — totals stay the
same on a single-stream host, only the split moves.  Leave it off for
throughput runs (the benchmark, ``chipbench/``, fences its own probes).
"""

from __future__ import annotations

import time
from contextlib import ContextDecorator
from typing import Any, ClassVar, Dict

from sheeprl_tpu.telemetry.spans import SPANS, TIMER_PHASES


class timer(ContextDecorator):
    disabled: ClassVar[bool] = False
    sync: ClassVar[bool] = False
    timers: ClassVar[Dict[str, float]] = {}
    _counts: ClassVar[Dict[str, int]] = {}

    def __init__(self, name: str, mode: str = "sum"):
        self.name = name
        self.mode = mode

    @classmethod
    def configure(cls, metric_cfg: Any) -> None:
        """Apply the ``metric.*`` timing knobs (every train loop calls this)."""
        cls.disabled = bool(
            metric_cfg.disable_timer or metric_cfg.log_level == 0
        )
        cls.sync = bool(metric_cfg.get("sync_timers", False))

    @staticmethod
    def _drain_device() -> None:
        """Block until every in-flight device computation has finished.

        ``utils.device_sync`` is ``block_until_ready`` over every live
        array."""
        try:
            from sheeprl_tpu.utils.utils import device_sync

            device_sync()
        except Exception:
            return  # timing must never take down the run

    def __enter__(self) -> "timer":
        if timer.sync and not timer.disabled:
            timer._drain_device()
        # phase-span bridge (telemetry/spans.py): the two timers every train
        # loop already wraps ARE the rollout / update.dispatch phases — one
        # mapping here wires all 12 loops.  Independent of `disabled`: spans
        # (and the tracer tick stream they drive) stay live at
        # metric.log_level=0, so a run that logs nothing still records them.
        phase = TIMER_PHASES.get(self.name)
        self._span = SPANS.push(phase) if phase is not None else None
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> bool:
        if self._span is not None:
            SPANS.pop(self._span)
        if not timer.disabled:
            if timer.sync:
                timer._drain_device()
            elapsed = time.perf_counter() - self._start
            if self.mode == "sum":
                timer.timers[self.name] = timer.timers.get(self.name, 0.0) + elapsed
            else:  # mean
                timer.timers[self.name] = timer.timers.get(self.name, 0.0) + elapsed
                timer._counts[self.name] = timer._counts.get(self.name, 0) + 1
        return False

    @classmethod
    def to_dict(cls, reset: bool = True) -> Dict[str, float]:
        out = {}
        for k, v in cls.timers.items():
            n = cls._counts.get(k)
            out[k] = v / n if n else v
        if reset:
            cls.timers = {}
            cls._counts = {}
        return out
