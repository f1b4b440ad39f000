"""CLI dispatchers.

Parity with the reference CLI layer (reference: sheeprl/cli.py:23-450):
``run`` (training), ``evaluation`` (from checkpoint), ``registration``
(model export) and ``available_agents`` — minus Hydra: composition is done
by :mod:`sheeprl_tpu.config.compose` with the same user-facing syntax.

Usage:
    python -m sheeprl_tpu exp=ppo env.id=CartPole-v1 fabric.devices=8
    python -m sheeprl_tpu --eval checkpoint_path=... [overrides...]
"""

from __future__ import annotations

import os
import pathlib
import sys
import warnings
from typing import List, Optional

from sheeprl_tpu.config.compose import ConfigError, compose
from sheeprl_tpu.utils.registry import (
    algorithm_registry,
    evaluation_registry,
    resolve_algorithm,
    resolve_entrypoint,
)
from sheeprl_tpu.utils.structured import deep_merge, dotdict


def import_extra_modules(cfg: dotdict) -> None:
    """Import user packages listed in ``algo.extra_modules`` so their
    ``@register_algorithm`` / ``@register_evaluation`` decorators run —
    the external-algorithm extension point (reference behavior:
    sheeprl/cli.py registration-at-import + howto/register_external_algorithm.md)."""
    import importlib

    for mod in cfg.get("algo", {}).get("extra_modules", []) or []:
        importlib.import_module(mod)


def check_configs(cfg: dotdict) -> None:
    """Config sanity validation (reference: sheeprl/cli.py:271-345)."""
    if "algo" not in cfg or cfg.algo.get("name") in (None, "???"):
        raise ConfigError(
            "No algorithm specified: pass exp=<experiment> or algo=<name> "
            f"(registered: {', '.join(sorted(algorithm_registry))})"
        )
    if algorithm_registry and cfg.algo.name not in algorithm_registry:
        raise ConfigError(
            f"Unknown algorithm '{cfg.algo.name}'. "
            f"Registered: {', '.join(sorted(algorithm_registry))}"
        )
    if "env" not in cfg or cfg.env.get("id") in (None, "???"):
        raise ConfigError("No environment specified: set env=<group> / env.id=<id>")
    for field in ("total_steps", "per_rank_batch_size"):
        if cfg.algo.get(field) in (None, "???"):
            raise ConfigError(f"algo.{field} must be set")
    strategy = cfg.fabric.get("strategy", "auto")
    if strategy not in ("auto", "dp"):
        warnings.warn(
            f"fabric.strategy='{strategy}' is not recognized; the runtime is a "
            "single-controller SPMD mesh ('auto'/'dp' are equivalent)",
            UserWarning,
        )


def resume_from_checkpoint(cfg: dotdict) -> dotdict:
    """Merge the previous run's saved config under the new one, keeping the
    user's total_steps / learning_starts overrides
    (reference: sheeprl/cli.py:23-57)."""
    import yaml

    ckpt_path = pathlib.Path(cfg.checkpoint.resume_from)
    old_cfg_path = ckpt_path.parent.parent / "config.yaml"
    if not old_cfg_path.is_file():
        return cfg
    with open(old_cfg_path) as f:
        old = yaml.safe_load(f)
    keep = {
        "total_steps": cfg.algo.get("total_steps"),
        "learning_starts": cfg.algo.get("learning_starts"),
    }
    merged = deep_merge(old, cfg.as_dict())
    out = dotdict(merged)
    for k, v in keep.items():
        if v is not None:
            out.algo[k] = v
    out.checkpoint.resume_from = str(ckpt_path)
    return out


def run_algorithm(cfg: dotdict) -> None:
    """Resolve, build the runtime, dispatch (reference: sheeprl/cli.py:60-199)."""
    import jax

    import sheeprl_tpu
    from sheeprl_tpu.parallel.fabric import build_fabric
    from sheeprl_tpu.telemetry import SPANS

    sheeprl_tpu.register_all_algorithms()
    import_extra_modules(cfg)
    entry = resolve_algorithm(cfg.algo.name, decoupled=cfg.fabric.get("decoupled"))
    entrypoint = resolve_entrypoint(entry)

    if cfg.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision", cfg.matmul_precision)
    with SPANS.setup_span("setup.fabric"):  # the backend's start
        fabric = build_fabric(cfg)
    entrypoint(fabric, cfg)
    _maybe_register_models(fabric, cfg)


def _maybe_register_models(fabric, cfg: dotdict) -> None:
    """End-of-training model export (reference: sheeprl/algos/*/…
    `register_model` hook at the end of every `main`, e.g. ppo.py:448-453):
    when ``model_manager.disabled`` is False, the final checkpoint's
    sub-models are registered with the configured names/descriptions."""
    mm = cfg.get("model_manager") or {}
    if mm.get("disabled", True) or (fabric is not None and not fabric.is_global_zero):
        return
    import glob

    from sheeprl_tpu.utils.checkpoint import load_checkpoint
    from sheeprl_tpu.utils.model_manager import register_model_from_checkpoint

    root = os.path.join(cfg.get("log_dir", "logs/runs"), str(cfg.get("root_dir")), str(cfg.get("run_name")))
    versions = sorted(
        glob.glob(os.path.join(root, "version_*")),
        key=lambda p: int(p.rsplit("_", 1)[-1]),
    )
    if not versions:
        return
    # ONLY the newest version dir — the one this run just wrote.  Falling
    # back to older runs would silently register stale weights when this
    # run saved no checkpoint (checkpoint.every=0, save_last=False).
    from sheeprl_tpu.checkpoint import latest_checkpoint

    newest = latest_checkpoint(os.path.join(versions[-1], "checkpoint"))
    if newest is None:
        # legacy flat-file layout (fabric.save / old runs)
        ckpts = sorted(
            glob.glob(os.path.join(versions[-1], "checkpoint", "*.ckpt")), key=os.path.getmtime
        )
        newest = ckpts[-1] if ckpts else None
    if newest is None:
        warnings.warn(
            "model_manager.disabled=False but the run saved no checkpoint; "
            "nothing registered", UserWarning
        )
        return
    state = load_checkpoint(newest)
    out = register_model_from_checkpoint(fabric, cfg, state)
    if out:
        print(f"Registered models from {newest}: {out}")


def resolve_resume_target(cfg: dotdict) -> dotdict:
    """Resolve ``checkpoint.resume_from=auto`` to the newest COMMITTED
    snapshot across every run/version under this experiment's root
    (``<log_dir>/<root_dir>``).  Torn snapshots (no COMMIT marker) are never
    eligible.  No committed snapshot → start fresh, with a warning."""
    if cfg.checkpoint.get("resume_from") != "auto":
        return cfg
    from sheeprl_tpu.checkpoint import resolve_auto_resume
    from sheeprl_tpu.checkpoint.protocol import verify_or_quarantine

    # a committed snapshot can still be damaged (bit rot, a torn write that
    # raced the manifest): verify the CRCs before trusting it, quarantine
    # (step_* → step_*.corrupt) on mismatch, and fall back to the next
    # newest committed snapshot instead of crashing the resume
    verify = bool(cfg.checkpoint.get("verify_on_resume", True))
    # quarantine can fail (read-only mount): a damaged snapshot that cannot
    # be renamed is EXCLUDED from re-resolution instead of re-tried forever,
    # so older intact commits are still found
    damaged: set = set()
    target = resolve_auto_resume(cfg.get("log_dir", "logs/runs"), cfg.root_dir)
    while target is not None and verify:
        problems = verify_or_quarantine(target)
        if not problems:
            break
        warnings.warn(
            f"checkpoint.resume_from=auto: {target} is damaged "
            f"({'; '.join(problems)}); trying the next committed snapshot",
            RuntimeWarning,
        )
        damaged.add(target)
        target = resolve_auto_resume(
            cfg.get("log_dir", "logs/runs"), cfg.root_dir, exclude=damaged
        )
    if target is None:
        warnings.warn(
            f"checkpoint.resume_from=auto: no committed checkpoint found under "
            f"{os.path.join(str(cfg.get('log_dir', 'logs/runs')), str(cfg.root_dir))}; "
            "starting fresh",
            UserWarning,
        )
        cfg.checkpoint.resume_from = None
    else:
        print(f"checkpoint.resume_from=auto -> {target}")
        cfg.checkpoint.resume_from = str(target)
    return cfg


def run(argv: Optional[List[str]] = None) -> None:
    # the run's own account of its set-up starts here: the root span `setup`
    # stays open until the loop's first iteration (docs/telemetry.md)
    from sheeprl_tpu import telemetry

    telemetry.SPANS.begin_setup()
    argv = list(sys.argv[1:] if argv is None else argv)
    # a preemption latched during a PREVIOUS run in this interpreter was
    # honored by that run's final save; this run starts un-preempted
    from sheeprl_tpu.checkpoint import PREEMPTION_GUARD

    PREEMPTION_GUARD.clear_latch()
    # same for the telemetry hub and flight recorder: a logger/step left
    # over from a previous run in this interpreter must not receive THIS
    # run's final flush, and a postmortem written by this run must hold
    # this run's events — not a previous drill's fault trail
    telemetry.HUB.reset()
    # a crashed loop never reached its sentinel teardown: drop the stale
    # run-scoped Health/* and Population/* sources so they cannot leak
    # into this run's flushes
    telemetry.HUB.unregister("health")
    telemetry.HUB.unregister("population")
    telemetry.RECORDER.clear()
    telemetry.COMPILE_MONITOR.install()  # before the first program of the run is built
    with telemetry.SPANS.setup_span("setup.compose"):
        cfg = compose(argv)
        # arm (or explicitly clear) the fault-injection plan before anything
        # else touches envs/checkpoints — SHEEPRL_FAULT_PLAN wins over the group
        from sheeprl_tpu.resilience import install_from_config

        install_from_config(cfg)
        cfg = resolve_resume_target(cfg)
        if cfg.checkpoint.get("resume_from"):
            cfg = resume_from_checkpoint(cfg)
    import sheeprl_tpu

    with telemetry.SPANS.setup_span("setup.register"):
        sheeprl_tpu.register_all_algorithms()
        import_extra_modules(cfg)
    check_configs(cfg)
    from sheeprl_tpu.utils.utils import print_config

    if cfg.get("print_config", True):
        print_config(cfg)
    try:
        run_algorithm(cfg)
    except BaseException as e:
        # every abnormal exit leaves evidence: the flight recorder dumps
        # its ring (injected faults, stalls, restarts, span edges, the
        # crash itself) as postmortem.json under the run dir
        telemetry.RECORDER.record("crash", error=f"{type(e).__name__}: {e}")
        telemetry.RECORDER.dump("exception")
        raise
    finally:
        if PREEMPTION_GUARD.requested():
            telemetry.RECORDER.record(
                "preemption", signal=PREEMPTION_GUARD.signal_name
            )
            telemetry.RECORDER.dump("preemption")
        # metrics buffered in the monitors since the last log interval
        # would otherwise be lost on any non-interval exit (exception,
        # preemption latch, dry-run) — land the final window through the
        # attached logger, then stop trace windows / the introspection
        # server.  Best-effort: telemetry never masks the real exception.
        telemetry.HUB.final_flush()
        telemetry.shutdown_run()


def evaluation(argv: Optional[List[str]] = None) -> None:
    """Evaluate a checkpoint (reference: sheeprl/cli.py:202-268, 369-405).

    Checkpoint discovery and snapshot→policy reconstruction go through
    ``sheeprl_tpu.serve.loader`` — the SAME path the policy server uses, so
    evaluation and serving can never disagree on how a snapshot is rebuilt.
    Algorithms with a registered serving player (ppo/sac/dreamer_v3
    families) evaluate through the serving player itself; the rest fall
    back to their ``@register_evaluation`` entrypoint, still fed by the
    loader's discovery + config resolution.
    """
    argv = list(sys.argv[1:] if argv is None else argv)
    ckpt_override = [a for a in argv if a.startswith("checkpoint_path=")]
    if not ckpt_override:
        raise ConfigError("evaluation requires checkpoint_path=<path-to-ckpt>")
    rest = [a for a in argv if not a.startswith("checkpoint_path=")]

    from sheeprl_tpu.serve.loader import load_policy, load_run_config, resolve_checkpoint
    from sheeprl_tpu.serve.players import PLAYER_BUILDERS

    ckpt_path = resolve_checkpoint(ckpt_override[0].split("=", 1)[1])
    cfg = load_run_config(ckpt_path, rest)
    if cfg.algo.name in PLAYER_BUILDERS:
        from sheeprl_tpu.serve.loader import evaluate_player
        from sheeprl_tpu.utils.logger import get_log_dir, get_logger

        fabric, cfg, _, player = load_policy(ckpt_path, rest, cfg=cfg)
        import_extra_modules(cfg)
        log_dir = get_log_dir(fabric, cfg.root_dir, cfg.run_name, base=cfg.get("log_dir", "logs/runs"))
        logger = get_logger(fabric, cfg, log_dir)
        evaluate_player(fabric, cfg, player, log_dir, logger)
        return

    # legacy registry path (algorithms without a serving player) — discovery
    # and config resolution above already came from the loader
    import sheeprl_tpu
    from sheeprl_tpu.parallel.fabric import build_fabric

    cfg.fabric.devices = 1
    cfg.env.num_envs = 1
    cfg.env.capture_video = cfg.env.get("capture_video", False)
    sheeprl_tpu.register_all_algorithms()
    import_extra_modules(cfg)
    entries = evaluation_registry.get(cfg.algo.name)
    if not entries:
        raise ConfigError(
            f"No evaluation registered for '{cfg.algo.name}' "
            f"(available: {', '.join(sorted(evaluation_registry))})"
        )
    entry = entries[0]
    import importlib

    module = importlib.import_module(entry.module)
    fn = getattr(module, entry.entrypoint)
    fabric = build_fabric(cfg)
    state = fabric.load(ckpt_path)
    fn(fabric, cfg, state)


def serve(argv: Optional[List[str]] = None) -> None:
    """Serve a committed checkpoint as a continuous-batching policy server.

    Usage:
        python -m sheeprl_tpu.serve checkpoint_path=<ckpt-or-run-dir> \\
            [serve.port=7455] [serve.batch_ladder=[1,8,32,128]] [overrides...]

    ``checkpoint_path`` accepts a committed ``step_*`` snapshot directory, a
    run/version directory (→ newest committed snapshot), or a legacy
    ``.ckpt`` file.  The server AOT-warms the policy executable at every
    batch-ladder rung before binding the socket, then hot-swaps params
    whenever training commits a newer snapshot into the same run directory.
    """
    argv = list(sys.argv[1:] if argv is None else argv)
    ckpt_override = [a for a in argv if a.startswith("checkpoint_path=")]
    if not ckpt_override:
        raise ConfigError("serve requires checkpoint_path=<ckpt-or-run-dir>")
    rest = [a for a in argv if not a.startswith("checkpoint_path=")]

    from sheeprl_tpu.serve import PolicyService
    from sheeprl_tpu.serve.server import PolicyServer

    # SHEEPRL_FAULT_PLAN plans arm BEFORE the checkpoint resolve/load so
    # startup-path sites (fabric.copy_to, the loader) are covered; a
    # config-group plan can only arm after the run config is loaded from
    # next to the checkpoint, i.e. it covers the serving phase only
    from sheeprl_tpu.resilience import install_from_config, install_from_env

    install_from_env()
    service = PolicyService.from_checkpoint(ckpt_override[0].split("=", 1)[1], rest)
    install_from_config(service.cfg)
    serve_cfg = service.cfg.get("serve") or {}
    server = PolicyServer(
        service,
        host=str(serve_cfg.get("host", "127.0.0.1")),
        port=int(serve_cfg.get("port", 7455)),
    )
    # flush: the smoke/CI parent parses this line off a block-buffered pipe
    # while serve_forever() never returns to flush it naturally
    print(
        f"serving {service.player.algo} (checkpoint step {service.store.step}) "
        f"on {server.url} — batch ladder {list(service.ladder)}, "
        f"commit watch {'on' if service.watcher else 'off'}",
        flush=True,
    )
    server.serve_forever()


def serve_fleet(argv: Optional[List[str]] = None) -> None:
    """Serve a committed checkpoint through the fault-tolerant fleet:
    N replica processes behind one health-checked router.

    Usage:
        python -m sheeprl_tpu.serve.fleet checkpoint_path=<run-dir> \\
            [serve.fleet.replicas=2] [serve.fleet.port=7456] [overrides...]

    Prefer a run/version directory over a pinned ``step_*`` snapshot: a
    respawned replica re-resolves ``checkpoint_path`` on its own, and a
    pinned step would come back serving stale params after a rolling
    reload.  See docs/serving.md "Fleet".
    """
    argv = list(sys.argv[1:] if argv is None else argv)
    ckpt_override = [a for a in argv if a.startswith("checkpoint_path=")]
    if not ckpt_override:
        raise ConfigError("serve_fleet requires checkpoint_path=<ckpt-or-run-dir>")
    ckpt_path = ckpt_override[0].split("=", 1)[1]
    rest = [a for a in argv if not a.startswith("checkpoint_path=")]

    from sheeprl_tpu.resilience import install_from_config, install_from_env
    from sheeprl_tpu.serve.fleet import FleetRouter, FleetServer, LocalFleet
    from sheeprl_tpu.serve.loader import (
        checkpoint_root,
        ensure_serve_config,
        load_run_config,
        resolve_checkpoint,
    )

    install_from_env()
    ckpt = resolve_checkpoint(ckpt_path)
    cfg = ensure_serve_config(load_run_config(ckpt, rest))
    install_from_config(cfg)
    serve_cfg = cfg.get("serve") or {}
    fleet_cfg = serve_cfg.get("fleet") or {}

    fleet = LocalFleet(
        ckpt_path,
        overrides=rest,
        replicas=int(fleet_cfg.get("replicas", 2)),
        respawn_max=int(fleet_cfg.get("respawn_max", 10)),
        backoff_base_s=float(fleet_cfg.get("respawn_backoff_base_s", 0.5)),
        backoff_max_s=float(fleet_cfg.get("respawn_backoff_max_s", 30.0)),
        seed=int(cfg.get("seed", 0) or 0),
    )

    # the replicas are OUR children: SIGTERM's default handler would kill
    # this process before the ``finally`` below reaps them, leaving N
    # orphaned servers bound to their ports — route it through SystemExit
    # so ``fleet.stop()`` runs and the exit is clean
    import signal

    signal.signal(signal.SIGTERM, lambda signum, frame: sys.exit(0))

    fleet.start()
    try:
        root = checkpoint_root(ckpt) if ckpt.is_dir() else None
        rolling = bool(fleet_cfg.get("rolling_reload", True))
        router = FleetRouter(fleet.addresses(), cfg, ckpt_root=root if rolling else None)
        fleet.attach(router)
        server = FleetServer(
            router,
            host=str(fleet_cfg.get("host", "127.0.0.1")),
            port=int(fleet_cfg.get("port", 7456)),
        )
        # flush: drills/CI parse this line off a block-buffered pipe while
        # serve_forever() never returns to flush it naturally
        print(
            f"fleet router over {fleet.n} replicas on {server.url} — "
            f"rolling reload {'on' if router.ckpt_root is not None else 'off'}, "
            f"replicas: {', '.join(f'{rid}={url}' for rid, url in sorted(fleet.addresses().items()))}",
            flush=True,
        )
        server.serve_forever()
    finally:
        fleet.stop()


def registration(argv: Optional[List[str]] = None) -> None:
    """Export checkpointed models to the model store
    (reference: sheeprl/cli.py:408-450)."""
    argv = list(sys.argv[1:] if argv is None else argv)
    ckpt_override = [a for a in argv if a.startswith("checkpoint_path=")]
    if not ckpt_override:
        raise ConfigError("registration requires checkpoint_path=<path-to-ckpt>")
    ckpt_path = pathlib.Path(ckpt_override[0].split("=", 1)[1])
    import yaml

    with open(ckpt_path.parent.parent / "config.yaml") as f:
        cfg = dotdict(yaml.safe_load(f))
    from sheeprl_tpu.config.compose import apply_cli_overrides

    apply_cli_overrides(cfg, [a for a in argv if not a.startswith("checkpoint_path=")])
    import importlib

    import sheeprl_tpu

    sheeprl_tpu.register_all_algorithms()
    import_extra_modules(cfg)
    entry = resolve_algorithm(cfg.algo.name)
    try:
        utils_mod = importlib.import_module(entry.module.rsplit(".", 1)[0] + ".utils")
    except ModuleNotFoundError:
        utils_mod = None
    from sheeprl_tpu.parallel.fabric import build_fabric
    from sheeprl_tpu.utils.model_manager import register_model_from_checkpoint

    fabric = build_fabric(cfg)
    state = fabric.load(ckpt_path)
    log_models = getattr(utils_mod, "log_models_from_checkpoint", None)
    if log_models is not None:
        log_models(fabric, cfg, state)
    else:
        keys = getattr(utils_mod, "MODELS_TO_REGISTER", None)
        versions = register_model_from_checkpoint(fabric, cfg, state, keys)
        print(f"Registered models: {versions}")


#: recurrent cores an algorithm's ``algo.core`` selects between (the first is its default)
ALGORITHM_CORES = {"ppo_recurrent": "lstm, decoder (exp=ppo_tokens)"}


def available_agents() -> None:
    """Print the registered algorithms (reference: sheeprl/available_agents.py:7-34)."""
    import sheeprl_tpu

    sheeprl_tpu.register_all_algorithms()
    try:
        from rich.console import Console
        from rich.table import Table

        table = Table(title="sheeprl-tpu agents")
        table.add_column("Algorithm")
        table.add_column("Module")
        table.add_column("Entrypoint")
        table.add_column("Decoupled")
        table.add_column("Cores")
        for name, entries in sorted(algorithm_registry.items()):
            for e in entries:
                table.add_row(name, e.module, e.entrypoint, str(e.decoupled), ALGORITHM_CORES.get(name, ""))
        Console().print(table)
    except Exception:
        for name, entries in sorted(algorithm_registry.items()):
            for e in entries:
                cores = f"\tcores={ALGORITHM_CORES[name]}" if name in ALGORITHM_CORES else ""
                print(f"{name}\t{e.module}\t{e.entrypoint}\tdecoupled={e.decoupled}{cores}")
