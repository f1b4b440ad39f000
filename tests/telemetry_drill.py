#!/usr/bin/env python
"""run_ci stage 11: live-introspection + postmortem drill.

Launches a short dreamer_v3 training run as a SUBPROCESS with
``telemetry.introspect.port=0`` armed and a seeded ``env.step`` raise
planted mid-run (``SHEEPRL_FAULT_PLAN``), then — while the run is alive —

1. parses the printed introspection URL off the child's stdout,
2. scrapes ``/metrics`` until the Prometheus exposition carries the
   compile counters (content type + text format asserted),
3. scrapes ``/v1/phase`` and checks the breakdown's fractions sum to ~1.0,

waits for the injected fault to kill the run (nonzero exit), and asserts
the run directory holds a well-formed ``postmortem.json`` whose event ring
contains the injected fault — the "every chaos path leaves evidence"
contract, exercised across a real process boundary.
"""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

LOG_DIR = "/tmp/run_ci_telemetry"

# raises at env.step invocation 40: comfortably after warm-up/compiles
# (scrape material exists) and comfortably inside the step budget below
FAULT_PLAN = json.dumps(
    {"seed": 3, "plan": [{"site": "env.step", "kind": "raise", "at": 40}]}
)

RUN_ARGS = [
    "exp=dreamer_v3",
    "algo=dreamer_v3_XS",
    "env=dummy",
    "env.id=discrete_dummy",
    "env.num_envs=2",
    "env.sync_env=True",
    "env.capture_video=False",
    # the dreamer exps arm RestartOnException (PR 8 chaos hardening), which
    # would absorb the planted raise — this drill needs the fault FATAL so
    # the crash path (postmortem dump + final flush) is what gets exercised
    "env.restart_on_exception=False",
    "algo.per_rank_batch_size=2",
    "algo.per_rank_sequence_length=8",
    "algo.horizon=4",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.mlp_keys.encoder=[state]",
    "algo.world_model.encoder.cnn_channels_multiplier=4",
    "algo.dense_units=16",
    "algo.world_model.recurrent_model.recurrent_state_size=16",
    "algo.world_model.transition_model.hidden_size=16",
    "algo.world_model.representation_model.hidden_size=16",
    "algo.learning_starts=8",
    "algo.total_steps=4096",  # the fault ends the run, not the budget
    "algo.replay_ratio=0.1",
    "algo.run_test=False",
    "fabric.devices=1",
    "fabric.accelerator=cpu",
    "checkpoint.every=0",
    "checkpoint.save_last=False",
    "buffer.memmap=False",
    "metric.log_level=1",
    "metric.log_every=1",
    "telemetry.introspect.port=0",
    f"log_dir={LOG_DIR}",
    "print_config=False",
]


def fetch(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.headers.get("Content-Type", ""), resp.read().decode()


def main() -> int:
    import shutil

    shutil.rmtree(LOG_DIR, ignore_errors=True)
    env = {
        **os.environ,
        "SHEEPRL_FAULT_PLAN": FAULT_PLAN,
        "JAX_PLATFORMS": "cpu",
    }
    child = subprocess.Popen(
        [sys.executable, "-m", "sheeprl_tpu", *RUN_ARGS],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env=env,
    )

    # drain stdout on a thread (the child must never block on a full pipe)
    lines: list = []
    url_found = threading.Event()

    def drain() -> None:
        for line in child.stdout:  # type: ignore[union-attr]
            lines.append(line)
            if "telemetry introspection on" in line:
                url_found.set()

    reader = threading.Thread(target=drain, daemon=True)
    reader.start()

    try:
        if not url_found.wait(timeout=180):
            raise AssertionError("child never printed the introspection URL")
        m = re.search(
            r"telemetry introspection on (http://\S+)", "".join(lines)
        )
        assert m, "URL line present but unparseable"
        url = m.group(1)
        print(f"[drill] scraping {url}")

        # /healthz answers immediately; /metrics carries the compile
        # counters once warm-up compiles have been recorded — poll for them
        status, _, body = fetch(url + "/healthz")
        assert status == 200 and json.loads(body)["ok"] is True

        deadline = time.monotonic() + 300
        ctype = metrics_body = None
        while time.monotonic() < deadline:
            if child.poll() is not None:
                raise AssertionError(
                    "child exited before /metrics showed compile counters:\n"
                    + "".join(lines[-30:])
                )
            status, ctype, metrics_body = fetch(url + "/metrics")
            assert status == 200
            if "sheeprl_compile_executables" in metrics_body:
                break
            time.sleep(2.0)
        assert metrics_body and "sheeprl_compile_executables" in metrics_body, (
            "compile counters never appeared in /metrics"
        )
        assert ctype == "text/plain; version=0.0.4; charset=utf-8", ctype
        assert re.search(
            r"^# TYPE sheeprl_compile_executables gauge$", metrics_body, re.M
        ), "Prometheus TYPE line missing"
        print("[drill] /metrics OK (content type + exposition format)")

        # poll /v1/phase until a phase span has closed (the first training
        # iteration opens rollout/update.dispatch via the timer bridge) —
        # the planted fault only fires mid-training, so one must appear
        phase = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline and child.poll() is None:
            status, _, body = fetch(url + "/v1/phase")
            assert status == 200
            phase = json.loads(body)
            if phase["phases"]:
                break
            time.sleep(2.0)
        assert phase is not None and phase["phases"], (
            "no phase span ever closed before the run died"
        )
        total = sum(p["frac"] for p in phase["phases"].values()) + phase["other_frac"]
        assert abs(total - 1.0) < 1e-3, f"phase fractions sum to {total}"
        print(f"[drill] /v1/phase OK (phases: {sorted(phase['phases'])}, Σfrac={total:.4f})")

        # now let the planted fault kill the run
        rc = child.wait(timeout=600)
        assert rc != 0, "the injected env.step fault should have killed the run"
        print(f"[drill] child died as planned (rc={rc})")
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=30)

    # the postmortem: well-formed, right reason, fault event in the ring
    pm_files = glob.glob(f"{LOG_DIR}/**/postmortem.json", recursive=True)
    assert pm_files, "crashed run left no postmortem.json\n" + "".join(lines[-30:])
    doc = json.load(open(pm_files[0]))
    assert doc["schema"] == "sheeprl.postmortem/1"
    assert doc["reason"] == "exception"
    kinds = [e["kind"] for e in doc["events"]]
    injected = [e for e in doc["events"] if e["kind"] == "fault.injected"]
    assert injected and injected[0]["site"] == "env.step", kinds
    assert any(e["kind"] == "crash" for e in doc["events"])
    assert doc["monitors"]["resilience"]["injected"] >= 1
    print(
        f"[drill] postmortem OK: {pm_files[0]} "
        f"({len(doc['events'])} events, reason={doc['reason']})"
    )
    print("telemetry drill OK: mid-run scrape + fault kill + postmortem evidence")
    return 0


if __name__ == "__main__":
    sys.exit(main())
