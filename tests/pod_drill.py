#!/usr/bin/env python
"""run_ci stage 16: pod-scale fault-tolerance drill (multi-controller).

A short decoupled-PPO run is driven as a REAL 2-process pod — the fake-DCN
protocol spawns a learner cell (rank 0) and an actor cell (rank 1), with
segments/params crossing a process boundary over the learner front — and
the :class:`~sheeprl_tpu.supervisor.PodSupervisor` supervises the whole
pod:

1. once the first snapshot COMMITs, the drill SIGKILLs the ACTOR cell —
   the "host" dies mid-window, exactly a preempted TPU worker;
2. the pod's collective failure semantics fire: no rank trains past a
   dead peer.  The supervisor's sidecar sees the dead cell and runs the
   coordinated teardown (the learner's preemption latch gets a chance at
   a final save; with rank 1 gone the snapshot cannot gather all shards,
   so it stays uncommitted — by design, a committed snapshot always
   represents the WHOLE pod);
3. the episode is classified restartable (``preempted`` via the learner's
   latch postmortem, or ``transient`` if the learner instead died on
   ``PeerLost``), and the supervisor relaunches BOTH ranks with
   ``checkpoint.resume_from=auto`` — a collective restart from the newest
   COMMIT under the shared root;
4. asserted: supervisor exit 0; the audit's crash episode carries the
   per-cell return codes (rank 1 killed by SIGKILL) and a restart action;
   the success episode completes; the newest COMMITTED snapshot sits at
   the FULL configured step count and verifies clean for both ranks.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import signal
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LOG_DIR = "/tmp/run_ci_pod"
TOTAL_STEPS = 128  # 16 learner updates x 8 policy steps each
WORLD = 2

RUN_ARGS = [
    "exp=ppo_decoupled",
    "env=dummy",
    "env.id=discrete_dummy",
    "env.max_episode_steps=16",
    "env.num_envs=2",
    "env.sync_env=True",
    "env.capture_video=False",
    "topology=pod",
    "topology.env_workers=2",
    "fabric.devices=auto",
    "fabric.accelerator=cpu",
    "fabric.distributed.heartbeat_grace_s=20",
    "algo.rollout_steps=4",
    "algo.per_rank_batch_size=8",
    # 4 epochs paces the learner: enough steady-state runway that the
    # SIGKILL lands mid-run, well before the final update
    "algo.update_epochs=4",
    f"algo.total_steps={TOTAL_STEPS}",
    "algo.mlp_keys.encoder=[state]",
    "algo.run_test=False",
    "checkpoint.every=16",
    "checkpoint.save_last=False",
    "checkpoint.commit_timeout_s=10",
    "buffer.memmap=False",
    "metric.log_level=1",
    "metric.log_every=1",
    f"log_dir={LOG_DIR}",
    "print_config=False",
    # drill pacing: tight backoff, learner heartbeat on a short leash
    "supervisor.max_restarts=3",
    "supervisor.backoff_base_s=0.2",
    "supervisor.poll_interval_s=1.0",
]


def main() -> int:
    shutil.rmtree(LOG_DIR, ignore_errors=True)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.supervisor import PodSupervisor

    cfg = compose(RUN_ARGS)
    sup = PodSupervisor(cfg, RUN_ARGS, WORLD)

    # -- the chaos: SIGKILL the actor "host" right after the first COMMIT ----
    killed: list = []

    def killer() -> None:
        while not killed:
            commits = glob.glob(os.path.join(LOG_DIR, "**", "COMMIT"), recursive=True)
            if commits:
                cells = list(sup._cells)
                if len(cells) == WORLD and cells[1].poll() is None:
                    cells[1].send_signal(signal.SIGKILL)
                    killed.append(sorted(commits))
                    print(f"[pod-drill] SIGKILLed actor cell after {commits[0]}", flush=True)
                    return
            time.sleep(0.05)

    threading.Thread(target=killer, name="pod-drill-killer", daemon=True).start()

    rc = sup.run()
    assert rc == 0, f"pod supervisor exited {rc} — the pod never completed"
    assert killed, "the drill never got to SIGKILL the actor cell"

    # -- audit trail: crash episode with per-cell rcs, then success ----------
    audit = sup.audit_path
    assert os.path.isfile(audit), f"no supervisor_log.jsonl at {audit}"
    episodes = [json.loads(line) for line in open(audit)]
    assert len(episodes) == 2, f"expected crash+success episodes, got {episodes}"
    crash, success = episodes
    assert crash["classification"] in ("preempted", "transient"), crash
    assert crash["action"] == "restart", crash
    assert crash["num_processes"] == WORLD, crash
    cell_rcs = {c["rank"]: c["returncode"] for c in crash["cells"]}
    assert cell_rcs[1] == -signal.SIGKILL, f"actor cell rc should be -9: {crash['cells']}"
    assert all(c["returncode"] is not None for c in crash["cells"]), (
        "coordinated teardown left a cell running: " + str(crash["cells"])
    )
    assert success["classification"] == "success" and success["returncode"] == 0, success
    print(f"[pod-drill] audit OK: {audit} ({len(episodes)} episodes, cells={crash['cells']})")

    # -- collective restart resumed from a shared commit and finished --------
    from sheeprl_tpu.checkpoint.protocol import checkpoint_step, step_dir_name, verify_checkpoint

    ckpt_dirs = glob.glob(os.path.join(sup.exp_root, "*", "version_*", "checkpoint"))
    steps = sorted(
        checkpoint_step(p)
        for d in ckpt_dirs
        for p in glob.glob(os.path.join(d, "step_*"))
        if checkpoint_step(p) >= 0 and os.path.exists(os.path.join(p, "COMMIT"))
    )
    assert steps, "no committed snapshots under the experiment root"
    assert steps[-1] == TOTAL_STEPS, (
        f"newest committed snapshot is step {steps[-1]}, expected {TOTAL_STEPS} (all: {steps})"
    )
    # the kill landed after the first commit; the resumed episode continued
    # that history rather than starting over
    assert len(steps) > 1, steps

    newest = next(
        os.path.join(d, step_dir_name(TOTAL_STEPS))
        for d in ckpt_dirs
        if os.path.exists(os.path.join(d, step_dir_name(TOTAL_STEPS)))
    )
    problems = verify_checkpoint(newest)
    assert not problems, f"final pod snapshot fails verification: {problems}"
    print(f"[pod-drill] checkpoints OK: committed steps {steps}; {newest} verifies clean")
    print(
        "pod drill OK: actor host SIGKILLed mid-window -> coordinated teardown "
        "-> collective restart from shared commit -> full step count"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
