"""Metric aggregation.

Host-side running aggregators with the same role as the reference's
torchmetrics-based ``MetricAggregator`` (reference: sheeprl/utils/metric.py:17-195):
a dict of named metrics that train loops ``update``, a ``compute`` that drops
NaNs/non-scalars, global disabling by log level, and a rank-independent
variant that gathers per-process values across hosts.

Device values are accepted lazily: ``update`` stores whatever it is given
(including not-yet-materialized ``jax.Array``s from inside the train step —
asynchronous dispatch means no sync happens until ``compute``), and
``compute`` coerces to float.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional

import numpy as np


class _RunningMetric:
    """One named accumulator: mode 'mean' | 'sum' | 'last' | 'max' | 'min'."""

    def __init__(self, mode: str = "mean"):
        if mode not in ("mean", "sum", "last", "max", "min"):
            raise ValueError(f"Unknown metric mode: {mode}")
        self.mode = mode
        self.reset()

    def reset(self) -> None:
        self._values: List[Any] = []

    def update(self, value: Any) -> None:
        self._values.append(value)

    @property
    def empty(self) -> bool:
        return not self._values

    def compute(self) -> Optional[float]:
        if not self._values:
            return None
        vals = []
        for v in self._values:
            arr = np.asarray(v, dtype=np.float64)
            if arr.size != 1:
                return None
            vals.append(float(arr.reshape(())))
        arr = np.asarray(vals)
        arr = arr[~np.isnan(arr)]
        if arr.size == 0:
            return None
        if self.mode == "mean":
            return float(arr.mean())
        if self.mode == "sum":
            return float(arr.sum())
        if self.mode == "last":
            return float(arr[-1])
        if self.mode == "max":
            return float(arr.max())
        return float(arr.min())


class MetricAggregator:
    disabled: bool = False

    def __init__(self, metrics: Optional[Dict[str, str]] = None, raise_on_missing: bool = False):
        self.metrics: Dict[str, _RunningMetric] = {}
        self.raise_on_missing = raise_on_missing
        for name, mode in (metrics or {}).items():
            self.add(name, mode)

    def add(self, name: str, mode: str = "mean") -> None:
        if name not in self.metrics:
            self.metrics[name] = _RunningMetric(mode if isinstance(mode, str) else "mean")

    def update(self, name: str, value: Any) -> None:
        if self.disabled:
            return
        if name not in self.metrics:
            if self.raise_on_missing:
                raise KeyError(f"Unregistered metric: {name}")
            return
        self.metrics[name].update(value)

    def pop(self, name: str) -> None:
        self.metrics.pop(name, None)

    def reset(self) -> None:
        for m in self.metrics.values():
            m.reset()

    def keys(self) -> Iterable[str]:
        return self.metrics.keys()

    def compute(self) -> Dict[str, float]:
        """Return finite scalar values only (NaNs and non-scalars dropped,
        like the reference compute, sheeprl/utils/metric.py:109-143)."""
        if self.disabled:
            return {}
        out: Dict[str, float] = {}
        for name, metric in self.metrics.items():
            if metric.empty:
                continue
            val = metric.compute()
            if val is not None and np.isfinite(val):
                out[name] = val
        return out


class RankIndependentMetricAggregator(MetricAggregator):
    """Aggregator whose ``compute`` first all-gathers values across processes
    (reference: sheeprl/utils/metric.py:146-195).  In the single-controller
    JAX runtime there is one process per host; cross-host gathering uses
    ``jax.experimental.multihost_utils`` when world_size > 1.
    """

    def __init__(self, *args: Any, fabric: Any = None, **kwargs: Any):
        super().__init__(*args, **kwargs)
        self._fabric = fabric

    def compute(self) -> Dict[str, float]:
        local = super().compute()
        if self._fabric is None or getattr(self._fabric, "world_size", 1) == 1:
            return local
        gathered = self._fabric.all_gather_object(local)
        merged: Dict[str, List[float]] = defaultdict(list)
        for d in gathered:
            for k, v in d.items():
                merged[k].append(v)
        return {k: float(np.mean(v)) for k, v in merged.items()}


def flush_metrics(
    aggregator: "MetricAggregator",
    timer_obj: Any,
    logger: Any,
    policy_step: int,
    last_log: int,
    extra_times: Optional[Dict[str, float]] = None,
    extra_metrics: Optional[Dict[str, float]] = None,
) -> int:
    """THE end-of-interval metric flush every train loop shares: compute+reset
    the aggregator, drain the named timers, derive the two SPS throughputs
    (reference: the identical block at e.g. sheeprl/algos/ppo/ppo.py:376-413 /
    dreamer_v3.py:715-730), merge ``extra_times`` (e.g. trainer-side times
    shipped over DCN in the dedicated decoupled topology) and
    ``extra_metrics`` (e.g. ``Params/replay_ratio``), log, and return the new
    ``last_log``.  The whole flush is the loop's ``log.flush`` span."""
    from sheeprl_tpu.telemetry.spans import span

    with span("log.flush", phase=False):
        return _flush_metrics(
            aggregator, timer_obj, logger, policy_step, last_log, extra_times, extra_metrics
        )


def _flush_metrics(aggregator, timer_obj, logger, policy_step, last_log, extra_times, extra_metrics) -> int:
    metrics = aggregator.compute()
    aggregator.reset()
    times = timer_obj.to_dict(reset=True)
    if extra_times:
        times = {**times, **{k: times.get(k, 0.0) + v for k, v in extra_times.items()}}
    steps_since = max(policy_step - last_log, 1)
    if "Time/env_interaction_time" in times:
        metrics["Time/sps_env_interaction"] = steps_since / max(times["Time/env_interaction_time"], 1e-9)
    if "Time/train_time" in times:
        metrics["Time/sps_train"] = steps_since / max(times["Time/train_time"], 1e-9)
    if extra_metrics:
        metrics.update(extra_metrics)
    metrics.update(times)
    # telemetry hub flush: every registered source in one call — the
    # compile-once accounting (Compile/*: a count that keeps growing after
    # warm-up IS the recompile pathology), checkpoint writer accounting
    # (Checkpoint/*: async-save cost), resilience accounting (Resilience/*:
    # empty unless something actually happened), the span tracker's
    # per-window phase-breakdown fractions (Phase/*), and anything a run
    # registered (Sebulba queues, the policy service).  roll=True closes
    # the span window — the metric interval IS the phase window.
    from sheeprl_tpu.telemetry.hub import HUB

    metrics.update(HUB.flush(roll=True))
    HUB.note_step(policy_step)
    if logger is not None and metrics:
        logger.log_metrics(metrics, policy_step)
    return policy_step
