"""Run loggers and versioned log directories.

Parity with the reference logger factory (reference: sheeprl/utils/logger.py:12-89):
rank-0 (process 0) creates ``<log_dir>/<root_dir>/<run_name>/version_k`` and, in
multi-host runs, broadcasts the chosen directory to other hosts so every
process logs/checkpoints consistently.  Backends: TensorBoard (tensorboardX)
or CSV (always-available fallback).
"""

from __future__ import annotations

import csv
import os
from typing import Any, Dict, Optional


class CSVLogger:
    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        os.makedirs(log_dir, exist_ok=True)
        self._path = os.path.join(log_dir, "metrics.csv")
        self._fieldnames = ["step", "name", "value"]
        if not os.path.exists(self._path):
            with open(self._path, "w", newline="") as f:
                csv.writer(f).writerow(self._fieldnames)

    def log_metrics(self, metrics: Dict[str, float], step: int) -> None:
        with open(self._path, "a", newline="") as f:
            w = csv.writer(f)
            for k, v in metrics.items():
                w.writerow([step, k, v])

    def log_hyperparams(self, params: Dict[str, Any]) -> None:
        import yaml

        with open(os.path.join(self.log_dir, "hparams.yaml"), "w") as f:
            yaml.safe_dump(params, f)

    def close(self) -> None:
        pass


class TensorBoardLogger:
    def __init__(self, log_dir: str):
        from tensorboardX import SummaryWriter

        self.log_dir = log_dir
        self.writer = SummaryWriter(log_dir)

    def log_metrics(self, metrics: Dict[str, float], step: int) -> None:
        for k, v in metrics.items():
            self.writer.add_scalar(k, v, step)

    def log_hyperparams(self, params: Dict[str, Any]) -> None:
        import yaml

        self.writer.add_text("hparams", "```\n" + yaml.safe_dump(params) + "\n```", 0)

    def log_video(self, tag: str, frames: Any, step: int, fps: int = 30) -> None:
        # frames: (T, H, W, C) uint8 → tensorboardX wants (N, T, C, H, W)
        import numpy as np

        vid = np.transpose(np.asarray(frames), (0, 3, 1, 2))[None]
        self.writer.add_video(tag, vid, step, fps=fps)

    def close(self) -> None:
        self.writer.close()


class MLflowLogger:
    """Thin adapter over the optional ``mlflow`` package (reference:
    sheeprl/configs/logger/mlflow.yaml + lightning MLFlowLogger). Requires
    ``mlflow`` to be installed and ``MLFLOW_TRACKING_URI`` (or the
    ``tracking_uri`` config key) to point at a tracking server."""

    def __init__(self, log_dir: str, experiment_name: str = "default",
                 tracking_uri: Optional[str] = None, run_name: Optional[str] = None):
        try:
            import mlflow
        except ImportError as e:  # pragma: no cover - mlflow absent from image
            raise ImportError(
                "metric.logger=mlflow requires the optional `mlflow` package; "
                "install it or use the tensorboard/csv loggers"
            ) from e
        self.log_dir = log_dir
        self._mlflow = mlflow
        if tracking_uri:
            mlflow.set_tracking_uri(tracking_uri)
        mlflow.set_experiment(experiment_name)
        self._run = mlflow.start_run(run_name=run_name)

    def log_metrics(self, metrics: Dict[str, float], step: int) -> None:
        self._mlflow.log_metrics({k.replace("/", "_"): float(v) for k, v in metrics.items()}, step=step)

    def log_hyperparams(self, params: Dict[str, Any]) -> None:
        flat = {}

        def walk(node, prefix=""):
            if isinstance(node, dict):
                for k, v in node.items():
                    walk(v, f"{prefix}{k}.")
            else:
                flat[prefix[:-1]] = node

        walk(params)
        # mlflow caps param batches; log defensively
        for k, v in flat.items():
            try:
                self._mlflow.log_param(k, v)
            except Exception:
                pass

    def close(self) -> None:
        self._mlflow.end_run()


def get_log_dir(fabric: Any, root_dir: str, run_name: str, base: str = "logs/runs") -> str:
    """Create (on process 0) and agree on a versioned run directory."""
    root = os.path.join(base, root_dir, run_name)
    if fabric is None or fabric.global_rank == 0:
        version = 0
        while os.path.isdir(os.path.join(root, f"version_{version}")):
            version += 1
        log_dir = os.path.join(root, f"version_{version}")
        os.makedirs(log_dir, exist_ok=True)
    else:
        log_dir = None
    if fabric is not None and (fabric.world_size > 1 or fabric.num_processes > 1):
        # num_processes matters independently of world_size: a pod of
        # single-device cells still needs every process to agree on rank
        # 0's version_N pick
        log_dir = fabric.broadcast_object(log_dir, src=0)
    return log_dir


def get_logger(fabric: Any, cfg: Any, log_dir: str) -> Optional[Any]:
    """Instantiate the configured logger on process 0 only.

    Also the central telemetry arm-point: every training loop (all 12
    algos, the Sebulba drivers, evaluation) constructs its logger here, so
    ``telemetry.setup_run`` — spans, trace windows, the flight recorder's
    run directory, the introspection endpoint — needs no per-loop wiring.
    The created logger is attached to the hub so the ``finally`` path of
    ``cli.run`` can land the last metric window after a crash."""
    from sheeprl_tpu import telemetry

    with telemetry.SPANS.setup_span("setup.logger"):
        telemetry.setup_run(
            cfg, log_dir, rank=fabric.global_rank if fabric is not None else 0
        )
        return _make_logger(fabric, cfg, log_dir)


def _make_logger(fabric: Any, cfg: Any, log_dir: str) -> Optional[Any]:
    from sheeprl_tpu import telemetry

    if fabric is not None and fabric.global_rank != 0:
        return None
    if getattr(cfg.metric, "log_level", 1) <= 0:
        return None
    kind = cfg.metric.logger.kind if "logger" in cfg.metric else "tensorboard"
    if kind == "tensorboard":
        try:
            logger = TensorBoardLogger(log_dir)
        except Exception:
            logger = CSVLogger(log_dir)
    elif kind == "csv":
        logger = CSVLogger(log_dir)
    elif kind == "mlflow":
        lcfg = cfg.metric.logger
        logger = MLflowLogger(
            log_dir,
            experiment_name=lcfg.get("experiment_name") or cfg.get("exp_name", "default"),
            tracking_uri=lcfg.get("tracking_uri"),
            run_name=lcfg.get("run_name"),
        )
    else:
        raise ValueError(f"Unknown logger kind: {kind}")
    telemetry.HUB.attach_logger(logger)
    return logger
