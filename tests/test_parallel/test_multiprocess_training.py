"""Full 2-process TRAINING smoke over ``jax.distributed`` (CPU backend).

Review round 1, weak #6: the host collectives were tested
2-process, but no actual training loop had ever run with
``jax.process_count() > 1`` — log-dir broadcast, per-process env sampling,
``host_local_array_to_global_array`` batch assembly, and per-rank
checkpointing all short-circuit single-process.  Here two real processes
run the PPO CLI end-to-end against each other on a 2-device global mesh
(1 local CPU device per process) — the same control flow a 2-host TPU pod
slice executes over DCN.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

_ALGO_ARGS = {
    "ppo": [
        "exp=ppo",
        "env.id=discrete_dummy",
        "algo.rollout_steps=4",
        "algo.update_epochs=1",
    ],
    "sac": [
        "exp=sac",
        "env.id=continuous_dummy",
        "algo.learning_starts=0",
        "algo.hidden_size=16",
    ],
    # global-pool minibatching across processes (reference ppo.py:363-370)
    "ppo_share_data": [
        "exp=ppo",
        "env.id=discrete_dummy",
        "algo.rollout_steps=4",
        "algo.update_epochs=2",
        "buffer.share_data=True",
    ],
    # dedicated cross-process player/trainer split: process 0 = envs-only
    # player, process 1 = trainer sub-mesh (reference decoupled topology,
    # sheeprl/algos/ppo/ppo_decoupled.py:623-670)
    "ppo_decoupled_dedicated": [
        "exp=ppo_decoupled",
        "env.id=discrete_dummy",
        "algo.rollout_steps=4",
        "algo.update_epochs=1",
        "algo.player.dedicated=True",
    ],
    # pixel obs exercise the (T,B,H,W,C) rollout layout on the trainer side
    # (obs_to_np rollout=True branch) — vector obs alone would miss it
    "ppo_decoupled_dedicated_pixels": [
        "exp=ppo_decoupled",
        "env.id=discrete_dummy",
        "algo.rollout_steps=4",
        "algo.update_epochs=1",
        "algo.player.dedicated=True",
        "algo.cnn_keys.encoder=[rgb]",
        "env.screen_size=32",
    ],
    "sac_decoupled_dedicated": [
        "exp=sac_decoupled",
        "env.id=continuous_dummy",
        "algo.learning_starts=0",
        "algo.hidden_size=16",
        "algo.player.dedicated=True",
        "algo.player.sync_every=1",
        "buffer.checkpoint=True",
    ],
    # vector-obs DreamerV3 (no CNN): exercises the sequential-replay block
    # assembly + per-rank sampling + PlayerSync paths multi-process
    "dreamer_v3": [
        "exp=dreamer_v3",
        "env.id=discrete_dummy",
        "algo=dreamer_v3_XS",
        "algo.learning_starts=0",
        "algo.replay_ratio=1",
        "algo.per_rank_sequence_length=8",
        "algo.horizon=4",
        "algo.cnn_keys.encoder=[]",
        "algo.dense_units=16",
        "algo.mlp_layers=1",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=16",
        "algo.world_model.transition_model.hidden_size=16",
        "algo.world_model.representation_model.hidden_size=16",
        "algo.world_model.discrete_size=4",
        "algo.world_model.stochastic_size=4",
        "buffer.size=400",
    ],
}

_WORKER = textwrap.dedent(
    """
    import glob, os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    # cross-process CPU computations need explicit collectives (default
    # "none" raises "Multiprocess computations aren't implemented on the
    # CPU backend" from the first broadcast)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=os.environ["COORD"],
        num_processes=int(os.environ.get("SMOKE_NPROC", "2")),
        process_id=int(sys.argv[1]),
    )
    from sheeprl_tpu.cli import run

    log_dir = os.environ["SMOKE_LOG_DIR"]
    run([
        *os.environ["SMOKE_ALGO_ARGS"].split(";"),
        "env=dummy",
        "dry_run=True",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        f"fabric.devices={os.environ.get('SMOKE_NPROC', '2')}",
        "fabric.accelerator=cpu",
        f"algo.per_rank_batch_size={os.environ.get('SMOKE_BATCH', '4')}",
        "algo.mlp_keys.encoder=[state]",
        "env.max_episode_steps=8",
        "algo.run_test=False",
        "metric.log_level=1",
        "metric.log_every=1",
        "checkpoint.every=1",
        "buffer.memmap=False",
        f"log_dir={log_dir}",
        "print_config=False",
    ])
    rank = jax.process_index()
    if rank == 0:
        from sheeprl_tpu.checkpoint import list_checkpoints

        ckpts = [
            c
            for root in glob.glob(f"{log_dir}/**/checkpoint", recursive=True)
            for c in list_checkpoints(root)
        ]
        assert ckpts, "rank 0 committed no checkpoint"
    print(f"rank {rank} TRAIN OK")
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
@pytest.mark.parametrize(
    "algo",
    [
        "ppo",
        "ppo_share_data",
        "sac",
        "dreamer_v3",
        "ppo_decoupled_dedicated",
        "ppo_decoupled_dedicated_pixels",
        "sac_decoupled_dedicated",
    ],
)
def test_two_process_training(tmp_path, algo):
    _run_distributed(tmp_path, _ALGO_ARGS[algo], nproc=2)


def _run_distributed(tmp_path, algo_args, nproc=2, batch=4, subdir="logs", timeout=420):
    port = _free_port()
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    log_dir = str(tmp_path / subdir)
    env = {
        **os.environ,
        "COORD": f"127.0.0.1:{port}",
        "SMOKE_ALGO_ARGS": ";".join(algo_args),
        "SMOKE_LOG_DIR": log_dir,
        "SMOKE_NPROC": str(nproc),
        "SMOKE_BATCH": str(batch),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(nproc)
    ]
    outputs = []
    for p in procs:
        out, _ = p.communicate(timeout=timeout)
        outputs.append(out)
    for i, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"rank {i} failed:\n{out}"
        assert f"rank {i} TRAIN OK" in out
    return log_dir


def _final_agent_params(log_dir):
    from sheeprl_tpu.utils.checkpoint import load_checkpoint
    from tests.ckpt_utils import find_checkpoints

    ckpts = find_checkpoints(log_dir)
    assert ckpts, f"no checkpoint under {log_dir}"
    return load_checkpoint(ckpts[-1])["agent"]


@pytest.mark.slow
def test_dedicated_three_process_two_trainers(tmp_path):
    """1 player + 2 trainers (VERDICT r2 #5): the lockstep rollout/weight
    broadcast protocol has to survive a trainer SUB-MESH of size 2, and the
    result must be seed-identical to the 1-trainer topology — the global
    batch is the same; only its sharding over trainers differs (GSPMD
    all-reduce ⇒ same update)."""
    import jax
    import numpy as np

    args = [
        "exp=ppo_decoupled",
        "env.id=discrete_dummy",
        "algo.rollout_steps=4",
        "algo.update_epochs=1",
        "algo.player.dedicated=True",
    ]
    # same GLOBAL minibatch (4): 1 trainer × 4/rank  vs  2 trainers × 2/rank
    dir_1t = _run_distributed(tmp_path, args, nproc=2, batch=4, subdir="logs_1t")
    dir_2t = _run_distributed(tmp_path, args, nproc=3, batch=2, subdir="logs_2t")
    p1 = _final_agent_params(dir_1t)
    p2 = _final_agent_params(dir_2t)
    flat1 = jax.tree_util.tree_leaves(p1)
    flat2 = jax.tree_util.tree_leaves(p2)
    assert len(flat1) == len(flat2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_dedicated_five_process_four_trainers(tmp_path):
    """1 player + 4 trainers (VERDICT r4 #9): trainer-count invariance must
    hold beyond the 2-trainer sub-mesh — same global minibatch (4) split as
    1×4 vs 4×1 must yield IDENTICAL final params (GSPMD all-reduce over a
    4-way data axis), and the 4-trainer checkpoint must remain evaluable
    through the eval CLI (reference N-rank topology:
    sheeprl/algos/ppo/ppo_decoupled.py:645-670)."""
    import glob

    import jax
    import numpy as np

    args = [
        "exp=ppo_decoupled",
        "env.id=discrete_dummy",
        "algo.rollout_steps=4",
        "algo.update_epochs=1",
        "algo.player.dedicated=True",
    ]
    dir_1t = _run_distributed(tmp_path, args, nproc=2, batch=4, subdir="logs_1t")
    dir_4t = _run_distributed(
        tmp_path, args, nproc=5, batch=1, subdir="logs_4t", timeout=600
    )
    p1 = _final_agent_params(dir_1t)
    p4 = _final_agent_params(dir_4t)
    flat1 = jax.tree_util.tree_leaves(p1)
    flat4 = jax.tree_util.tree_leaves(p4)
    assert len(flat1) == len(flat4)
    for a, b in zip(flat1, flat4):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    from sheeprl_tpu.cli import evaluation
    from tests.ckpt_utils import find_checkpoints

    ckpts = find_checkpoints(dir_4t)
    evaluation(
        [
            f"checkpoint_path={ckpts[-1]}",
            "env.capture_video=False",
            "fabric.accelerator=cpu",
            f"log_dir={tmp_path / 'eval_4t'}",
        ]
    )


@pytest.mark.slow
def test_dedicated_three_process_sac(tmp_path):
    """SAC dedicated topology with 2 trainers: protocol survives (deadlock /
    skew smoke at >1 trainer; off-policy sampling is rank-decorrelated so
    exact equivalence is not expected here)."""
    _run_distributed(
        tmp_path,
        [
            "exp=sac_decoupled",
            "env.id=continuous_dummy",
            "algo.learning_starts=0",
            "algo.hidden_size=16",
            "algo.player.dedicated=True",
            "algo.player.sync_every=1",
        ],
        nproc=3,
        batch=2,
        subdir="logs_sac3",
    )
