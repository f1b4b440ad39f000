"""CI smoke for the policy server CLI (run_ci.sh stage 7).

Trains a tiny committed dryrun checkpoint, launches the REAL
``python -m sheeprl_tpu.serve`` process on an ephemeral port, streams a
burst of concurrent HTTP requests through the continuous batcher, checks
the stats surface, and asserts a clean SIGINT shutdown (exit code 0).
"""

import os
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.serve.client import PolicyClient
    from tests.ckpt_utils import find_checkpoints

    log_dir = tempfile.mkdtemp(prefix="serve_smoke_")
    run(
        [
            "exp=ppo", "env=dummy", "env.id=discrete_dummy", "dry_run=True",
            "env.num_envs=2", "env.sync_env=True", "env.capture_video=False",
            "fabric.devices=1", "fabric.accelerator=cpu", "metric.log_level=0",
            "checkpoint.every=1", "buffer.memmap=False",
            f"log_dir={log_dir}", "print_config=False", "algo.run_test=False",
        ]
    )
    ckpt = find_checkpoints(log_dir)[-1]
    print(f"[serve_smoke] committed checkpoint: {ckpt}")

    proc = subprocess.Popen(
        [
            sys.executable, "-m", "sheeprl_tpu.serve",
            f"checkpoint_path={ckpt}", "serve.port=0", "serve.max_wait_ms=2",
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        url = None
        deadline = time.monotonic() + 300
        for line in proc.stdout:
            print(f"[server] {line.rstrip()}")
            m = re.search(r"on (http://[\d.]+:\d+)", line)
            if m:
                url = m.group(1)
                break
            if time.monotonic() > deadline:
                raise TimeoutError("server never announced its address")
        assert url, f"server exited early (rc={proc.poll()})"

        client = PolicyClient(url, timeout=120.0)
        for _ in range(60):  # the socket accepts once the ladder is warm
            try:
                health = client.health()
                break
            except Exception:
                time.sleep(1.0)
        else:
            raise TimeoutError("server never became healthy")
        assert health["ok"] and health["algo"] == "ppo", health

        obs = {
            k: np.zeros(shape, np.dtype(dt))
            for k, (shape, dt) in health["obs_spec"].items()
        }
        action_shape = tuple(health["action_shape"])
        errors = []

        def worker():
            try:
                a = client.act(obs, greedy=True)
                assert a.shape == action_shape, a
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        assert not errors, errors

        stats = client.stats()
        print(f"[serve_smoke] stats: {stats}")
        assert stats["served"] >= 24 and stats["errors"] == 0, stats
        assert np.isfinite(stats["p50_ms"]) and np.isfinite(stats["p99_ms"]), stats

        # the telemetry-hub export on the serve surface (PR 13): the same
        # stats in Prometheus text exposition format at /metrics
        import urllib.request

        with urllib.request.urlopen(url + "/metrics", timeout=30) as resp:
            assert resp.status == 200
            ctype = resp.headers.get("Content-Type", "")
            body = resp.read().decode()
        assert ctype == "text/plain; version=0.0.4; charset=utf-8", ctype
        assert "sheeprl_serve_served" in body, body[:400]
        print("[serve_smoke] /metrics OK (Prometheus exposition via the telemetry hub)")

        proc.send_signal(signal.SIGINT)
        rc = proc.wait(60)
        assert rc == 0, f"server exited rc={rc} on SIGINT (expected clean shutdown)"
        print("[serve_smoke] OK: served batched HTTP traffic, clean shutdown")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(10)


if __name__ == "__main__":
    main()
