#!/usr/bin/env python
"""run_ci stage 15: fault-tolerant serving-fleet drill.

A tiny committed PPO snapshot is served by a REAL 2-replica fleet
(``LocalFleet`` spawning ``python -m sheeprl_tpu.serve`` twice) behind a
``FleetRouter``/``FleetServer`` front, then attacked three ways at once:

1. **injected replica faults** — a seeded ``serve.replica`` raise plan
   fires on the router→replica leg every few forwards, so failover runs
   continuously, not just at the kill;
2. **replica murder** — one replica is SIGKILLed mid-stream; the
   supervisor respawns it, the router ejects/readmits it;
3. **poisoned rollout** — a newer checkpoint with a flipped shard byte is
   committed (the watcher's CRC verify must reject it before ANY replica
   is asked to reload), followed by a good commit that must roll out to
   every replica.

Gates: zero dropped requests, every session completes, the router's
stats/metrics show the failovers and the halted-then-completed rollout,
and both replicas end up serving the new step.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LOG_DIR = "/tmp/run_ci_fleet"
N_CLIENTS = 8
N_REQUESTS = 30

# fires in the ROUTER process only (the forward leg) — replicas inherit the
# env var but never call these sites
FAULT_PLAN = json.dumps(
    {"seed": 7, "plan": [{"site": "serve.replica", "kind": "raise", "every": 23}]}
)


def _train_tiny() -> str:
    from sheeprl_tpu.cli import run
    from tests.ckpt_utils import find_checkpoints

    run(
        [
            "exp=ppo",
            "env=dummy",
            "env.id=discrete_dummy",
            "dry_run=True",
            "env.num_envs=2",
            "env.sync_env=True",
            "env.capture_video=False",
            "fabric.devices=1",
            "fabric.accelerator=cpu",
            "metric.log_level=0",
            "checkpoint.every=1",
            "buffer.memmap=False",
            "algo.learning_starts=0",
            f"log_dir={LOG_DIR}",
            "print_config=False",
            "algo.run_test=False",
        ]
    )
    ckpts = find_checkpoints(LOG_DIR)
    assert ckpts, f"dryrun produced no committed checkpoint under {LOG_DIR}"
    return str(ckpts[-1])


def main() -> int:
    shutil.rmtree(LOG_DIR, ignore_errors=True)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    ckpt = _train_tiny()
    # plant the chaos plan AFTER training (the trainer would otherwise trip
    # over plan validation for serve-only sites it never fires)
    os.environ["SHEEPRL_FAULT_PLAN"] = FAULT_PLAN
    from sheeprl_tpu.resilience.faults import install_from_env

    install_from_env()

    import numpy as np

    from sheeprl_tpu.checkpoint.protocol import (
        checkpoint_step,
        shard_name,
        step_dir_name,
        write_commit,
        write_shard,
    )
    from sheeprl_tpu.serve.client import PolicyClient
    from sheeprl_tpu.serve.fleet import FleetRouter, FleetServer, LocalFleet
    from sheeprl_tpu.serve.loader import checkpoint_root, resolve_checkpoint

    ckpt_path = resolve_checkpoint(ckpt)
    root = checkpoint_root(ckpt_path)
    base_step = checkpoint_step(ckpt_path)
    assert root is not None and base_step >= 0, (ckpt_path, base_step)

    cfg = {
        "serve": {
            "fleet": {
                "health_poll_s": 0.2,
                "eject_threshold": 2,
                "readmit_s": 0.5,
                "route_retries": 3,
                "request_timeout_s": 60.0,
                "drain_timeout_s": 10.0,
                "reload_poll_s": 3600.0,  # rollouts driven by hand below
            }
        }
    }
    fleet = LocalFleet(
        str(ckpt_path),
        overrides=["serve.batch_ladder=[1,8]", "serve.max_wait_ms=2"],
        replicas=2,
        backoff_base_s=0.2,
        backoff_max_s=1.0,
        echo=False,
    )
    fleet.start()
    server = None
    try:
        router = FleetRouter(fleet.addresses(), cfg, ckpt_root=root)
        fleet.attach(router)
        server = FleetServer(router)
        server.start()
        assert router.wait_healthy(min_replicas=2, timeout=120.0), router.health()
        print(f"[drill] fleet up: 2 replicas behind {server.url}")

        # -- phase 1: chaos load (injected faults + SIGKILL mid-stream) ------
        health = PolicyClient(server.url, timeout=120.0).health()
        obs = {
            k: np.zeros(shape, np.dtype(dt))
            for k, (shape, dt) in health["obs_spec"].items()
        }
        errors, done = [], []
        barrier = threading.Barrier(N_CLIENTS + 1)

        def client_thread(cid: int) -> None:
            client = PolicyClient(server.url, timeout=120.0, retries=6, retry_base_s=0.2)
            barrier.wait(timeout=120.0)
            try:
                for _ in range(N_REQUESTS):
                    client.act(obs, greedy=True, session=f"drill-{cid}")
                    time.sleep(0.05)
                done.append(cid)
            except Exception as e:  # noqa: BLE001 — the gate IS "no exception"
                errors.append((cid, repr(e)))

        threads = [
            threading.Thread(target=client_thread, args=(i,)) for i in range(N_CLIENTS)
        ]
        for t in threads:
            t.start()
        barrier.wait(timeout=120.0)
        time.sleep(0.4)
        fleet.kill(0, sig=signal.SIGKILL)
        print("[drill] replica r0 SIGKILLed mid-stream")
        for t in threads:
            t.join(300.0)
        assert not errors, f"dropped requests: {errors}"
        assert sorted(done) == list(range(N_CLIENTS)), "a session failed to complete"
        stats = router.stats()
        assert stats["routed"] >= N_CLIENTS * N_REQUESTS, stats
        assert stats["failovers"] >= 1, stats
        print(
            f"[drill] chaos load OK: {stats['routed']} routed, "
            f"{stats['failovers']} failovers, {stats['ejects']} ejects, 0 drops"
        )

        # the supervisor must bring slot r0 back before the rollout phase
        # (the rollout skips unprobed slots; the point is reloading BOTH)
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            if sum(1 for r in router.replica_list() if r.routable) >= 2:
                break
            time.sleep(0.5)
        routable = sum(1 for r in router.replica_list() if r.routable)
        assert routable == 2, f"respawned replica never readmitted: {router.health()}"
        assert router.stats()["respawns"] >= 1, router.stats()
        print("[drill] respawn OK: killed replica is back and routable")

        # -- phase 2: poisoned rollout halts before any replica --------------
        state = {"agent": {"w": np.arange(32, dtype=np.float64)}}
        poison_step = base_step + 100
        poison_dir = root / step_dir_name(poison_step)
        poison_dir.mkdir()
        write_shard(poison_dir, 0, state)
        assert write_commit(poison_dir, poison_step, world=1, timeout_s=30.0)
        shard = poison_dir / shard_name(0)
        raw = bytearray(shard.read_bytes())
        raw[len(raw) // 2] ^= 0xFF
        shard.write_bytes(bytes(raw))

        code, payload = router.reload_once()
        assert code == 200 and payload["reloaded"] is False, payload
        assert router._fleet_store.step == base_step, router._fleet_store.step
        per_replica = router.health()["per_replica"]
        for rid, desc in per_replica.items():
            assert desc["checkpoint_step"] == base_step, (rid, desc)
        print(f"[drill] poison OK: step {poison_step} rejected, fleet still at {base_step}")

        # -- phase 3: a good commit rolls out to every replica ---------------
        good_step = base_step + 200
        good_dir = root / step_dir_name(good_step)
        good_dir.mkdir()
        # replicas reload a REAL snapshot: reuse the served checkpoint's
        # payload so the player rebuild succeeds
        import pickle

        with open(ckpt_path / shard_name(0), "rb") as f:
            good_state = pickle.load(f)
        write_shard(good_dir, 0, good_state)
        assert write_commit(good_dir, good_step, world=1, timeout_s=30.0)

        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline and router._fleet_store.step != good_step:
            router.reload_once()
            time.sleep(0.5)  # reload breaker cool-down after the poison
        assert router._fleet_store.step == good_step, (
            router._fleet_store.step,
            router.watcher.last_error,
        )
        for rid, desc in router.health()["per_replica"].items():
            assert desc["checkpoint_step"] == good_step, (rid, desc)
        stats = router.stats()
        assert stats["rolling_reloads"] >= 1, stats
        assert stats["reload_halts"] == 0, stats  # poison never reached a replica
        print(f"[drill] rolling reload OK: both replicas serve step {good_step}")

        # -- metrics surface --------------------------------------------------
        import urllib.request

        with urllib.request.urlopen(server.url + "/metrics", timeout=30) as resp:
            body = resp.read().decode()
        for needle in (
            "sheeprl_fleet_replicas 2.0",
            "sheeprl_fleet_failovers",
            "sheeprl_fleet_respawns",
            "sheeprl_fleet_rolling_reloads",
        ):
            assert needle in body, f"{needle!r} missing from /metrics"
        print(
            "fleet drill OK: injected faults + SIGKILL + poisoned commit -> "
            "0 drops, respawn readmitted, rollout halted on poison and "
            "completed on the good commit"
        )
        return 0
    finally:
        if server is not None:
            server.stop()
        fleet.stop()


if __name__ == "__main__":
    sys.exit(main())
