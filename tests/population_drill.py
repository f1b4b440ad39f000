#!/usr/bin/env python
"""run_ci stage 17: the PBT-beats-fixed-hyperparams drill (ISSUE 20).

Two seeded population=4 CartPole PPO runs at EQUAL env steps through the
real CLI, differing in exactly one knob:

* **pbt** — in-trace exploit/explore armed (``population.exploit_every``):
  truncation selection copies the top member's params+opt-state over the
  bottom member's and perturbs its hyperparams, inside the ONE fused
  executable (``algo.max_recompiles=1`` + the armed transfer guard gate
  the compile-once / zero-H2D law the whole time);
* **fixed** — ``population.exploit_every=0``: the same seeded log-uniform
  hyperparameter spread, trained to the end with no selection — the
  classic fixed-hyperparam control arm.

Gate: the PBT arm's best member must beat the fixed arm's WORST member on
final fitness (the episode-return EMA from the fused carry).  That is the
minimal honest claim PBT makes — selection reallocates the budget of the
doomed members — and it must hold at this tiny scale for the subsystem to
be worth its complexity.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# runnable as `python tests/population_drill.py` without an install
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LOG_ROOT = "/tmp/run_ci_population"

COMMON = [
    "exp=ppo",
    "env=jax_cartpole",
    "env.num_envs=4",
    "seed=42",
    "algo.rollout_steps=32",
    "algo.per_rank_batch_size=32",
    "algo.update_epochs=1",
    "algo.mlp_keys.encoder=[state]",
    "algo.total_steps=40000",
    "algo.max_recompiles=1",
    "algo.run_test=False",
    "population.size=4",
    # a wide seeded init spread: the doomed members are REALLY doomed
    # (lr down to 0.05x base), so selection has signal to act on
    "population.init_min=0.05",
    "population.init_max=2.0",
    "population.warmup=8",
    "fabric.devices=1",
    "fabric.accelerator=cpu",
    "env.sync_env=True",
    "env.capture_video=False",
    "checkpoint.every=0",
    "checkpoint.save_last=False",
    "buffer.memmap=False",
    "metric.log_level=1",
    "metric.log_every=5000",
    "print_config=False",
]


def _summary(log_dir: str) -> dict:
    hits = glob.glob(os.path.join(log_dir, "**", "population_summary.json"), recursive=True)
    assert len(hits) == 1, f"expected one population_summary.json under {log_dir}, got {hits}"
    with open(hits[0]) as f:
        return json.load(f)


def main() -> int:
    from sheeprl_tpu.utils.utils import force_cpu_backend

    force_cpu_backend()
    from sheeprl_tpu.cli import run

    shutil.rmtree(LOG_ROOT, ignore_errors=True)

    arms = {
        "pbt": ["population.exploit_every=8"],
        "fixed": ["population.exploit_every=0"],
    }
    results = {}
    for name, extra in arms.items():
        log_dir = os.path.join(LOG_ROOT, name)
        run([*COMMON, *extra, f"log_dir={log_dir}"])
        results[name] = _summary(log_dir)
        print(
            f"[population_drill] {name}: fitness={['%.1f' % f for f in results[name]['fitness']]} "
            f"exploits={results[name]['exploit_events']}"
        )

    pbt, fixed = results["pbt"], results["fixed"]
    # sanity: the control arm really was selection-free, the PBT arm wasn't
    assert fixed["exploit_events"] == 0, f"control arm exploited: {fixed['exploit_events']}"
    assert pbt["exploit_events"] > 0, "PBT arm never exploited — cadence/warmup misconfigured"
    # both arms completed identical member episodes budgets (equal env steps
    # is by construction: same total_steps, same population size)
    assert pbt["best_fitness"] > fixed["worst_fitness"], (
        f"PBT best member ({pbt['best_fitness']:.2f}) failed to beat the worst "
        f"fixed-hyperparam member ({fixed['worst_fitness']:.2f})"
    )
    print(
        f"population drill OK: PBT best {pbt['best_fitness']:.1f} > "
        f"fixed worst {fixed['worst_fitness']:.1f} at equal env steps "
        f"({pbt['exploit_events']} exploit events)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
