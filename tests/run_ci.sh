#!/usr/bin/env bash
# The one-command CPU test gate (runs in CI — .github/workflows/cpu-tests.yaml —
# and locally).  Parity role model: the reference's pinned suite
# (/root/reference/.github/workflows/cpu-tests.yaml:25-65 + tests/run_tests.py).
#
# Every stage runs under its own WALL BUDGET (`timeout`): a wedged stage —
# exactly the failure class the resilience layer exists for — kills that
# stage with rc=124 instead of hanging the whole gate.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"
# workers for XLA:CPU's intra-op pool: 8 virtual devices waiting at concurrent
# collectives must not hold them all (tests/conftest.py says why)
export NPROC="${NPROC:-32}"

echo "=== stage 1/17: unit + E2E dry-run suite (budget 1500s) ==="
timeout -k 15 1500 python -m pytest tests/ -x -q \
  --ignore=tests/test_regression --ignore=tests/test_checkpoint \
  --ignore=tests/test_resilience

echo "=== stage 2/17: fault-tolerant checkpointing (commit protocol + SIGTERM/resume drill) (budget 420s) ==="
timeout -k 15 420 python -m pytest tests/test_checkpoint -q

echo "=== stage 3/17: chaos drills (fault injection: env storm, SIGKILL+quarantine resume, serve under faults) (budget 600s) ==="
timeout -k 15 600 python -m pytest tests/test_resilience -q

echo "=== stage 4/17: numeric regression (goldens + reference fixture) (budget 600s) ==="
timeout -k 15 600 python -m pytest tests/test_regression -q

echo "=== stage 5/17: multichip dryrun (virtual 8-device mesh) (budget 900s) ==="
timeout -k 15 900 python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "=== stage 6/17: 2-D (data x model) mesh training cell + compile budget (budget 600s) ==="
# dreamer_v3 end-to-end through the CLI on a 2x4 fake-device mesh: the
# partition-rules (TP) path with the recompile detector as a hard gate —
# algo.max_recompiles=1 means each compile-once program (train phase, player
# step) may compile at most twice (first compile free + the prefill/train
# signature split); a TP path that regressed to recompile-per-step dies here.
timeout -k 15 600 python - <<'PY'
from sheeprl_tpu.cli import run
run([
    "exp=dreamer_v3", "env=dummy", "env.id=discrete_dummy",
    "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[]",
    "algo.horizon=4", "algo.dense_units=16", "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=4",
    "algo.world_model.recurrent_model.recurrent_state_size=32",
    "algo.world_model.transition_model.hidden_size=32",
    "algo.world_model.representation_model.hidden_size=32",
    "algo.world_model.discrete_size=4", "algo.world_model.stochastic_size=4",
    "algo.per_rank_batch_size=4", "algo.per_rank_sequence_length=8",
    "algo.learning_starts=16", "algo.total_steps=32", "algo.replay_ratio=0.5",
    "algo.max_recompiles=1", "algo.run_test=False",
    "env.num_envs=2", "env.sync_env=True", "env.capture_video=False",
    "fabric.devices=8", "fabric.accelerator=cpu",
    "fabric.mesh_shape={data: 2, model: 4}",
    "checkpoint.every=0", "checkpoint.save_last=False", "buffer.memmap=False",
    "metric.log_level=0", "log_dir=/tmp/run_ci_tp_logs", "print_config=False",
])
print("stage 6/17 OK: dreamer_v3 trained on a 2x4 data x model mesh within the compile budget")
PY

echo "=== stage 7/17: policy-serving smoke (HTTP server + batched requests + clean shutdown) (budget 600s) ==="
timeout -k 15 600 python tests/serve_smoke.py

echo "=== stage 8/17: zero-copy device replay (dreamer_v3 + sac, transfer guard armed) (budget 900s) ==="
# Coupled dreamer_v3 and sac train SHORT real runs (not dryruns: the guard
# only means something once steady-state windows exist) with the
# device-resident replay forced on, jax.transfer_guard("disallow") armed
# around every post-warmup train window (buffer.transfer_guard=true), and
# the recompile budget at 1 — a steady state that ships a batch H2D, or a
# cursor that churns the executable signature, dies here red.
timeout -k 15 900 python - <<'PY'
from sheeprl_tpu.cli import run
common = [
    "env=dummy", "env.num_envs=2", "env.sync_env=True", "env.capture_video=False",
    "fabric.devices=2", "fabric.accelerator=cpu",
    "buffer.memmap=False", "buffer.size=1024", "buffer.device=True",
    "buffer.transfer_guard=True", "checkpoint.every=0", "checkpoint.save_last=False",
    "metric.log_level=0", "algo.max_recompiles=1", "algo.run_test=False",
    "print_config=False",
]
run([
    "exp=dreamer_v3", "env.id=discrete_dummy", "env.action_repeat=1",
    "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[]",
    "algo.horizon=4", "algo.dense_units=16", "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=4",
    "algo.world_model.recurrent_model.recurrent_state_size=32",
    "algo.world_model.transition_model.hidden_size=32",
    "algo.world_model.representation_model.hidden_size=32",
    "algo.world_model.discrete_size=4", "algo.world_model.stochastic_size=4",
    "algo.per_rank_batch_size=4", "algo.per_rank_sequence_length=8",
    "algo.learning_starts=16", "algo.total_steps=64", "algo.replay_ratio=0.5",
    "log_dir=/tmp/run_ci_replay_dv3",
] + common)
print("stage 8 dv3 OK: zero-copy steady state under transfer guard")
run([
    "exp=sac", "env.id=continuous_dummy",
    "algo.learning_starts=16", "algo.total_steps=96", "algo.replay_ratio=0.5",
    "algo.per_rank_batch_size=8",
    "log_dir=/tmp/run_ci_replay_sac",
] + common)
print("stage 8/17 OK: dreamer_v3 + sac trained zero-copy under the transfer guard")
PY

echo "=== stage 9/17: scenario matrix (every algo x {cpu-gym, jax-env, dummy} x {coupled, decoupled}) (budget 1500s) ==="
# The enforced grid from ROADMAP item 5: each cell is an end-to-end dryrun
# under algo.max_recompiles=1 (compile budget) and a per-cell wall budget
# (tests/scenario_matrix.py prints the full coverage table, including the
# pruned cells and why).  The jax column drives BOTH rollout modes of the
# on-policy loops: Anakin fused and the JaxToGymAdapter fallback.
timeout -k 15 1500 python tests/scenario_matrix.py

echo "=== stage 10/17: sebulba actor-learner topology (2-actor/2-learner fake-device split) (budget 600s) ==="
# ISSUE 12: decoupled PPO trains end-to-end through the Sebulba device
# split — env-worker threads feeding batched AOT actor inference on the
# actor group, the learner sub-mesh consuming the device-resident
# trajectory queue, learner->actor D2D param broadcast — under
# algo.max_recompiles=1 (executable-signature churn in the actor ladder,
# the learner phase, or the broadcast dies here red).
timeout -k 15 600 python - <<'SEB'
from sheeprl_tpu.cli import run
run([
    "exp=ppo_decoupled", "env=dummy", "env.id=discrete_dummy",
    "env.max_episode_steps=16", "env.num_envs=4", "env.sync_env=True",
    "env.capture_video=False",
    "topology=sebulba", "topology.actor_devices=2", "topology.learner_devices=2",
    "topology.env_workers=2",
    "fabric.devices=4", "fabric.accelerator=cpu",
    "algo.rollout_steps=4", "algo.per_rank_batch_size=8",
    "algo.update_epochs=1", "algo.total_steps=64",
    "algo.mlp_keys.encoder=[state]", "algo.max_recompiles=1",
    "algo.run_test=False", "checkpoint.every=0", "checkpoint.save_last=False",
    "buffer.memmap=False", "metric.log_level=1", "metric.log_every=1",
    "print_config=False", "log_dir=/tmp/run_ci_sebulba",
])
print("stage 10/17 OK: ppo_decoupled trained through the sebulba 2-actor/2-learner split within the compile budget")
SEB

echo "=== stage 11/17: telemetry drill (live /metrics + /v1/phase scrape, fault kill, postmortem evidence) (budget 600s) ==="
# ISSUE 13: a short dv3 run with telemetry.introspect.port armed is scraped
# MID-RUN (/metrics Prometheus exposition + /v1/phase breakdown summing to
# ~1.0), then a planted env.step fault kills it and the run dir must hold a
# well-formed postmortem.json containing the injected-fault event.
timeout -k 15 600 python tests/telemetry_drill.py

echo "=== stage 12/17: supervisor drill (fatal fault -> classified restart -> auto-resume -> full step count) (budget 600s) ==="
# ISSUE 14: a supervised SAC run is killed mid-run by a planted env.step
# fault; the supervisor classifies the crash off postmortem.json, restarts
# with checkpoint.resume_from=auto, and the resumed run completes with the
# FULL configured step count — the audit trail (supervisor_log.jsonl) and
# the monotone committed-checkpoint history are asserted.
timeout -k 15 600 python tests/supervisor_drill.py

echo "=== stage 13/17: graftlint static analysis (zero unsuppressed findings, strict baseline) (budget 120s) ==="
# ISSUE 15: the JAX-law analyzer over the whole package — use-after-donate
# (the PR 7/PR 14 bug class), trace purity, PRNG discipline, and the
# config/fault-site/metric registries.  --strict also fails on STALE
# baseline entries: a fixed finding must take its ledger entry with it.
timeout -k 15 120 python -m sheeprl_tpu.analysis --strict

echo "=== stage 14/17: pipelined world-model training cell (2-stage x 2-data mesh) (budget 600s) ==="
# ISSUE 16: dreamer_v3 end-to-end through the CLI with the pipeline group
# live — a pipeline mesh axis composing with the partition rules, the
# world-model update running as the in-trace 1F1B microbatch schedule
# (pipeline=2stage: S=2, M=4) — under algo.max_recompiles=1: a schedule
# that broke the compile-once law or leaked an H2D transfer dies here red.
timeout -k 15 600 python - <<'PIPE'
from sheeprl_tpu.cli import run
run([
    "exp=dreamer_v3", "env=dummy", "env.id=discrete_dummy",
    "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[]",
    "algo.horizon=4", "algo.dense_units=16", "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=4",
    "algo.world_model.recurrent_model.recurrent_state_size=32",
    "algo.world_model.transition_model.hidden_size=32",
    "algo.world_model.representation_model.hidden_size=32",
    "algo.world_model.discrete_size=4", "algo.world_model.stochastic_size=4",
    "algo.per_rank_batch_size=4", "algo.per_rank_sequence_length=8",
    "algo.learning_starts=16", "algo.total_steps=32", "algo.replay_ratio=0.5",
    "algo.max_recompiles=1", "algo.run_test=False",
    "env.num_envs=2", "env.sync_env=True", "env.capture_video=False",
    "fabric.devices=4", "fabric.accelerator=cpu",
    "fabric.mesh_shape={data: 2, pipeline: 2}",
    "pipeline=2stage",
    "checkpoint.every=0", "checkpoint.save_last=False", "buffer.memmap=False",
    "metric.log_level=0", "log_dir=/tmp/run_ci_pipeline_logs", "print_config=False",
])
print("stage 14/17 OK: dreamer_v3 trained 1F1B on a 2-stage x 2-data mesh within the compile budget")
PIPE

echo "=== stage 15/17: serving-fleet chaos drill (kill -9 + injected faults + poisoned rollout -> zero drops) (budget 900s) ==="
# ISSUE 17: a REAL 2-replica fleet (LocalFleet subprocesses behind the
# FleetRouter front) under concurrent session load takes injected
# serve.replica faults AND a SIGKILL mid-stream — zero dropped requests,
# every session completes, the killed replica respawns and is readmitted;
# then a poisoned (bit-flipped) newer commit must halt the rolling reload
# before ANY replica touches it, and a good commit must roll out to all.
timeout -k 15 900 python tests/fleet_drill.py

echo "=== stage 16/17: pod fault-tolerance drill (2-host fake DCN, SIGKILLed host -> collective restart -> full step count) (budget 900s) ==="
# ISSUE 19: a REAL 2-process pod (fake-DCN learner + actor cells, segments
# and params crossing the process boundary over the learner front) is
# supervised end to end: the actor "host" is SIGKILLed right after the
# first COMMIT — the pod's collective failure semantics tear every rank
# down (no rank trains past a dead peer), the PodSupervisor classifies
# the episode restartable and relaunches BOTH ranks with
# checkpoint.resume_from=auto, and the resumed pod completes the FULL
# step count from the newest shared commit, verifying clean for all ranks.
timeout -k 15 900 python tests/pod_drill.py

echo "=== stage 17/17: population drill (in-trace PBT beats fixed hyperparams at equal env steps) (budget 900s) ==="
# ISSUE 20: two seeded population=4 CartPole PPO runs — whole population
# vmapped inside ONE donated-carry fused executable (algo.max_recompiles=1)
# — with in-trace exploit/explore armed vs population.exploit_every=0 (the
# fixed-hyperparam control).  The PBT arm's best member must beat the
# control arm's worst member on final fitness; anything else means the
# selection machinery is dead weight.
timeout -k 15 900 python tests/population_drill.py

echo "CI gate: ALL GREEN"
