#!/usr/bin/env python
"""run_ci stage 12: self-healing supervisor drill.

A short SAC training run is supervised end-to-end across a REAL process
boundary (``sheeprl_tpu.supervisor`` spawning ``python -m sheeprl_tpu``):

1. a seeded ``env.step`` raise is planted at invocation 40 (mid-run, well
   past several committed checkpoints) via ``SHEEPRL_FAULT_PLAN`` — the
   fault is FATAL (``env.restart_on_exception`` defaults off for SAC), so
   episode 0 crashes with a postmortem;
2. the supervisor classifies the crash (transient: first occurrence of
   that fatal signature), restarts with ``checkpoint.resume_from=auto``,
   and the resumed episode — whose remaining iterations never reach
   invocation 40 again — runs to completion;
3. asserted: supervisor exit 0; ``supervisor_log.jsonl`` holds exactly
   the crash episode (classification ``transient``, action ``restart``,
   a postmortem path whose document carries the injected fault) and the
   success episode; and the experiment root's newest COMMITTED snapshot
   sits at the FULL configured step count — the run lost nothing but the
   uncommitted tail.

This is the loop PRs 2/8/13 could not close alone: the crash leaves
evidence (PR 13), the evidence names a committed snapshot (PR 2), and now
something acts on it without a human.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LOG_DIR = "/tmp/run_ci_supervisor"
TOTAL_STEPS = 64  # 32 iterations x 2 envs
FAULT_AT = 40  # env.step invocation 40 = iteration 20: past the step-32 commit

FAULT_PLAN = json.dumps(
    {"seed": 5, "plan": [{"site": "env.step", "kind": "raise", "at": FAULT_AT}]}
)

RUN_ARGS = [
    "exp=sac",
    "env=dummy",
    "env.id=continuous_dummy",
    "env.num_envs=2",
    "env.sync_env=True",
    "env.capture_video=False",
    "algo.learning_starts=8",
    f"algo.total_steps={TOTAL_STEPS}",
    "algo.replay_ratio=0.5",
    "algo.per_rank_batch_size=8",
    "algo.run_test=False",
    "fabric.devices=1",
    "fabric.accelerator=cpu",
    "checkpoint.every=8",
    "checkpoint.save_last=True",
    "buffer.memmap=False",
    "buffer.size=512",
    "metric.log_level=1",
    "metric.log_every=1",
    f"log_dir={LOG_DIR}",
    "print_config=False",
    # drill pacing: tight backoff, no long watchdog interplay
    "supervisor.max_restarts=3",
    "supervisor.backoff_base_s=0.2",
    "supervisor.poll_interval_s=1.0",
]


def main() -> int:
    shutil.rmtree(LOG_DIR, ignore_errors=True)
    os.environ["SHEEPRL_FAULT_PLAN"] = FAULT_PLAN
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.supervisor import Supervisor

    cfg = compose(RUN_ARGS)
    sup = Supervisor(cfg, RUN_ARGS)
    rc = sup.run()
    assert rc == 0, f"supervisor exited {rc} — the supervised run never completed"

    # -- audit trail ---------------------------------------------------------
    audit = sup.audit_path
    assert os.path.isfile(audit), f"no supervisor_log.jsonl at {audit}"
    episodes = [json.loads(line) for line in open(audit)]
    assert len(episodes) == 2, f"expected crash+success episodes, got {episodes}"
    crash, success = episodes
    assert crash["classification"] == "transient", crash
    assert crash["action"] == "restart", crash
    assert crash["returncode"] not in (0, None), crash
    assert success["classification"] == "success" and success["returncode"] == 0, success
    print(f"[drill] audit OK: {audit} ({len(episodes)} episodes)")

    # -- the crash left evidence and the supervisor read it ------------------
    assert crash["postmortem"], "crash episode has no postmortem path"
    doc = json.load(open(crash["postmortem"]))
    assert doc["schema"].startswith("sheeprl.postmortem/")
    assert any(
        e.get("kind") == "fault.injected" and e.get("site") == "env.step"
        for e in doc["events"]
    ), "postmortem does not show the injected env.step fault"
    assert crash["signature"], "crash verdict carries no fatal signature"
    print(f"[drill] postmortem OK: {crash['postmortem']}")

    # -- the run finished with the FULL configured step count ----------------
    from sheeprl_tpu.checkpoint.protocol import checkpoint_step

    steps = sorted(
        checkpoint_step(p)
        for p in glob.glob(os.path.join(sup.exp_root, "*", "version_*", "checkpoint", "step_*"))
        if checkpoint_step(p) >= 0
    )
    assert steps, "no committed snapshots under the experiment root"
    assert steps[-1] == TOTAL_STEPS, (
        f"newest committed snapshot is step {steps[-1]}, expected {TOTAL_STEPS} "
        f"(all: {steps})"
    )
    # the resumed episode must have CONTINUED, not restarted from zero: a
    # from-scratch rerun would re-commit the early steps into its own run
    # dir — instead the pre-crash commits and the post-resume commits must
    # interleave into one monotone history
    assert TOTAL_STEPS - 8 in steps or len(set(steps)) > 1, steps
    print(f"[drill] checkpoints OK: committed steps {steps}")
    print(
        "supervisor drill OK: fatal mid-run fault -> postmortem-classified "
        "restart -> auto-resume -> full step count"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
