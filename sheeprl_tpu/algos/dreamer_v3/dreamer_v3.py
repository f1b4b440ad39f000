"""DreamerV3 — world-model RL, the TPU-critical path (SURVEY.md §3.3, §7.6).

Capability parity with the reference train script
(reference: sheeprl/algos/dreamer_v3/dreamer_v3.py:48-780): RSSM world model
with balanced-KL reconstruction training, imagination-based actor/critic
with two-hot returns, percentile return normalization (Moments), target
critic EMA (τ=0.02), Ratio-governed replay, sequential replay with per-env
streams, episode bookkeeping with reset rows, learning-starts prefill.

TPU-native architecture:
* the RSSM sequence loop and the imagination horizon are ``lax.scan``s
  (the reference runs Python loops over time, dreamer_v3.py:115-145/235-241);
* ALL gradient steps of a ratio window run in ONE jitted dispatch: the
  host samples a ``(U, L, B, *)`` block in one call (the reference's own
  bulk-sample pattern, dreamer_v3.py:664-671) and the device scans over U
  full updates (world model + actor + critic + EMA);
* the environment player is a latent-state policy on ``algo.player.device``
  (``auto``: beside the train state when a refresh would pull more than
  ``PLAYER_PULL_BYTES`` to the host, as every preset from S up does; the
  host CPU below that), refreshed once per ratio window: one on-device
  tree copy beside the train state, one packed transfer to the host;
* replay lives ON DEVICE (``buffer.device``, data/device_replay.py): the
  whole ring — pixels included — is a mesh-sharded HBM pytree, and
  sequence sampling compiles INTO the update dispatch, so steady-state
  training performs zero H2D (supersedes the retired pixel-only
  ``DeviceMirror``); on the host fallback images ship uint8 and normalize
  on device; batches shard over the mesh ``data`` axis, params replicated
  (GSPMD gradient all-reduce), and the Moments quantile is computed on
  the global batch — which IS the reference's all-gathered Moments
  semantics (utils.py:56-63).
"""

from __future__ import annotations

import os
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.dreamer_v3.agent import WorldModel, build_agent
from sheeprl_tpu.algos.dreamer_v3.loss import world_model_loss
from sheeprl_tpu.algos.dreamer_v3.utils import (
    compute_lambda_values,
    moments_update,
    normalize_obs_block,
    prepare_obs,
    test,
)
from sheeprl_tpu.algos.ppo.utils import actions_for_env, spaces_to_dims
from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, EpisodeBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.device_replay import (
    DeviceReplay,
    build_device_replay,
    fused_sequence_train,
    resolve_device_replay,
    sampled_bytes,
    steady_guard,
    update_chunks,
)
from sheeprl_tpu.parallel.fabric import PlayerSync
from sheeprl_tpu.parallel.pipeline import (
    chunked_rows,
    merge_microbatches,
    pipeline_value_and_grad,
    register_pipeline_metrics,
    resolve_pipeline,
    split_microbatches,
    stage_batch_constraint,
)
from sheeprl_tpu.telemetry.spans import SPANS
from sheeprl_tpu.utils.distribution import (
    Bernoulli,
    MSEDistribution,
    OneHotCategorical,
    SymlogDistribution,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.utils.env import episode_stats, final_obs_rows, make_env, vectorize
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, flush_metrics
from sheeprl_tpu.utils.optim import build_optimizer
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import (
    Ratio,
    merge_framestack,
    save_configs,
    window_scan,
)


def build_dv3_optimizers(fabric, cfg, params, saved_opt_state=None):
    """Optimizers + (replicated) opt state for the three param groups —
    shared by main(), __graft_entry__.py and the mesh tests so the program
    they check is the training program."""
    with SPANS.setup_span("setup.optimizer"):
        wm_opt = build_optimizer(cfg.algo.world_model.optimizer, cfg.algo.world_model.clip_gradients)
        actor_opt = build_optimizer(cfg.algo.actor.optimizer, cfg.algo.actor.clip_gradients)
        critic_opt = build_optimizer(cfg.algo.critic.optimizer, cfg.algo.critic.clip_gradients)
        # shard_params, not replicate: under TP the optimizer moments share the
        # kernels' shapes, so the same column-sharding rule places them
        # consistently with their params (no-op on a pure-data mesh)
        opt_state = fabric.shard_params(
            saved_opt_state
            or {
                "world_model": wm_opt.init(params["world_model"]),
                "actor": actor_opt.init(params["actor"]),
                "critic": critic_opt.init(params["critic"]),
            }
        )
    return wm_opt, actor_opt, critic_opt, opt_state


@register_algorithm()
def main(fabric: Any, cfg: Any) -> None:
    dreamer_family_loop(fabric, cfg, build_agent, make_train_phase)


def dreamer_family_loop(
    fabric: Any,
    cfg: Any,
    build_agent_fn: Any,
    make_train_phase_fn: Any,
    optimizer_builder: Any = None,
    initial_state: Any = None,
) -> None:
    """Shared env/replay/dispatch loop of the Dreamer family (V1/V2/V3 and
    the P2E variants differ in modules and losses, not in this loop —
    mirroring how the reference keeps per-version mains structurally
    identical)."""
    rank = fabric.global_rank
    key = fabric.seed_everything(cfg.seed)

    # pipeline parallelism is wired through the dreamer_v3 train-phase
    # builder only: fail HERE (build time, clear message) for the other
    # family members, and surface the schedule shape as Pipeline/* metrics
    pipe = resolve_pipeline(cfg)
    pipe.check_algo(cfg.algo.name)
    if pipe.enabled:
        register_pipeline_metrics(pipe)

    log_dir = get_log_dir(fabric, cfg.root_dir, cfg.run_name, base=cfg.get("log_dir", "logs/runs"))
    logger = get_logger(fabric, cfg, log_dir)
    ckpt_mgr = fabric.get_checkpoint_manager(cfg, log_dir)
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    # ---------------- environments (restart-wrapped like the reference,
    # dreamer_v3.py:385-400) --------------------------------------------------
    num_envs = cfg.env.num_envs
    envs = vectorize(
        cfg,
        [
            make_env(cfg, cfg.seed + rank * num_envs + i, rank, run_name=log_dir, vector_env_idx=i)
            for i in range(num_envs)
        ],
    )
    obs_space = envs.single_observation_space
    act_space = envs.single_action_space
    actions_dim, is_continuous = spaces_to_dims(act_space)
    act_width = int(sum(actions_dim))
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys

    # ---------------- agent / optimizers ------------------------------------
    state: Dict[str, Any] = dict(initial_state or {})
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)
    if state and state.get("key") is not None:
        # resume the train-dispatch RNG stream bit-exactly (rank-identical)
        key = jnp.asarray(state["key"])
    with SPANS.setup_span("setup.agent"):
        world_model, actor, critic, params = build_agent_fn(
            fabric, actions_dim, is_continuous, cfg, obs_space, state.get("agent")
        )
    WM = type(world_model)
    builder = optimizer_builder or build_dv3_optimizers
    wm_opt, actor_opt, critic_opt, opt_state = builder(
        fabric, cfg, params, state.get("opt_state")
    )

    aggregator = MetricAggregator(cfg.metric.aggregator.metrics if cfg.metric.log_level > 0 else {})
    timer.configure(cfg.metric)

    psync = PlayerSync(
        fabric, cfg, extract=lambda p: {"world_model": p["world_model"], "actor": p["actor"]},
        params=params,
    )
    host = psync.device  # single resolution of algo.player.device
    stoch_flat = world_model.stoch_flat
    rec_size = cfg.algo.world_model.recurrent_model.recurrent_state_size

    # ---------------- host player --------------------------------------------
    # MineDojo-style action masking: the mask observations (exposed as mlp
    # keys) constrain the player's sampling (reference: MinedojoActor)
    use_action_masks = bool(cfg.algo.actor.get("action_masks", False))
    mask_keys = ("mask_action_type", "mask_craft_smelt", "mask_equip_place", "mask_destroy")

    def player_step_fn(p, carry, obs, k, greedy=False):
        """(h, z, prev_action) carry; returns new carry + env-space action +
        the advanced key (advancing it in-program saves two host dispatches
        per env step)."""
        h, z, prev_a = carry
        with jax.named_scope("player.step"):
            k_repr, k_act, k_next = jax.random.split(k, 3)
            embed = world_model.apply(p["world_model"], obs, method=WM.encode)
            is_first = jnp.zeros((h.shape[0], 1))
            h, z, _, _ = world_model.apply(
                p["world_model"], h, z, prev_a, embed, is_first, k_repr, method=WM.dynamic
            )
            latent = jnp.concatenate([z, h], -1)
            head = actor.apply(p["actor"], latent)
            if use_action_masks:
                action = actor.sample_masked(
                    head, k_act, {mk: obs[mk] for mk in mask_keys}, greedy=greedy
                )
            else:
                action = actor.sample(head, k_act, greedy=greedy)
        return (h, z, action), action, k_next

    # compile-once routing: the player executable is AOT-compiled per
    # abstract signature and counted by the recompile detector
    player_step = fabric.compile(
        player_step_fn,
        name=f"{cfg.algo.name}.player_step",
        static_argnames=("greedy",),
        max_recompiles=cfg.algo.get("max_recompiles"),
    )

    def init_player_carry(batch: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        return (
            np.zeros((batch, rec_size), np.float32),
            np.zeros((batch, stoch_flat), np.float32),
            np.zeros((batch, act_width), np.float32),
        )

    player_params = psync.init(params)
    player_carry = init_player_carry(num_envs)

    def player_test_step(p, carry, obs, k, greedy):
        if carry is None:
            carry = tuple(jnp.zeros_like(jnp.asarray(c[:1])) for c in init_player_carry(1))
        carry, action, _ = player_step(p, carry, obs, k, greedy=greedy)
        a = np.asarray(action)
        if not is_continuous:
            # one-hot branches → index per branch
            idx, start = [], 0
            for d in actions_dim:
                idx.append(a[..., start:start + d].argmax(-1))
                start += d
            a = np.stack(idx, axis=-1).astype(np.float32)
        return carry, a

    # ---------------- single-dispatch multi-update train phase ---------------
    train_phase = make_train_phase_fn(
        fabric, cfg, world_model, actor, critic, wm_opt, actor_opt, critic_opt,
        cnn_keys=cnn_keys, mlp_keys=mlp_keys, is_continuous=is_continuous,
        params=params, opt_state=opt_state,
    )
    # training-health sentinels (resilience/health.py): wrap the compiled
    # phase (it inlines under the guard's trace) with the non-finite guard +
    # divergence detector, threading the tiny device HealthState first.
    # Covers every dreamer-family entry point — the p2e builders need no
    # changes.  health.enabled=false keeps the exact unguarded program.
    from sheeprl_tpu.resilience.health import DivergenceError, HealthSentinel

    sentinel = HealthSentinel.from_config(cfg, fabric)
    if sentinel is not None:
        sentinel.register()
        train_phase = fabric.compile(
            sentinel.wrap(train_phase),
            name=f"{cfg.algo.name}.train_phase_guarded",
            donate_argnums=(0, 1, 2),
            max_recompiles=cfg.algo.get("max_recompiles"),
        )

    # ---------------- replay buffer ------------------------------------------
    seq_len = int(cfg.algo.per_rank_sequence_length)
    batch_size = int(cfg.algo.per_rank_batch_size) * fabric.local_world_size
    train_phase_dev = None  # the fused sample+update program (device replay only)
    if cfg.buffer.get("type", "sequential") == "episode":
        rb = EpisodeBuffer(
            max(int(cfg.buffer.size), seq_len * 4),
            sequence_length=seq_len,
            n_envs=num_envs,
            prioritize_ends=bool(cfg.buffer.get("prioritize_ends", False)),
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}")
            if cfg.buffer.memmap
            else None,
        )
    else:
        capacity = max(int(cfg.buffer.size) // num_envs, seq_len * 2)
        memmap_dir = (
            os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None
        )
        # device-resident replay (data/device_replay.py): the WHOLE ring —
        # pixels included — lives in HBM sharded over the mesh `data` axis,
        # and sequence sampling compiles into the update dispatch.  This
        # subsumes the retired per-device DeviceMirror (pixel-only,
        # probe-gated) and the H2D window_chunks byte budget: in steady
        # state nothing ships per update.  The EpisodeBuffer layout (no
        # ring) and CPU runs keep the host-numpy path.
        if resolve_device_replay(cfg, fabric.accelerator):
            # fold on-device sequence sampling + block prep INTO the compiled
            # update (data/device_replay.fused_sequence_train): the
            # (U, L, B, *) block is gathered from the HBM ring inside the
            # dispatch — the layout/uint8 normalization contract of the host
            # path is reproduced by _prep_blocks
            def _prep_blocks(b):
                out = {}
                for kk in cnn_keys:
                    x = b[kk]
                    if x.ndim == 7:  # (U, L, B, S, H, W, C) framestack
                        x = merge_framestack(x, jnp)
                    out[kk] = x  # uint8 rides to the train phase; /255 on device
                for kk in mlp_keys:
                    x = b[kk].astype(jnp.float32)
                    out[kk] = x.reshape(*x.shape[:3], -1)
                out["actions"] = b["actions"].astype(jnp.float32)
                for kk in ("rewards", "terminated", "is_first"):
                    out[kk] = b[kk][..., 0].astype(jnp.float32)
                return out

            def _make_fused(ring):
                return fused_sequence_train(
                    fabric,
                    train_phase,
                    ring,
                    batch_size,
                    seq_len,
                    _prep_blocks,
                    name=f"{cfg.algo.name}.train_phase_device",
                    max_recompiles=cfg.algo.get("max_recompiles"),
                    health=sentinel is not None,
                )

            # the ring's rows, exactly as the loop below stores them
            leaf_specs = {
                k: (tuple(obs_space[k].shape) or (1,), obs_space[k].dtype) for k in obs_keys
            }
            for k in ("rewards", "terminated", "truncated", "is_first"):
                leaf_specs[k] = ((1,), np.float32)
            leaf_specs["actions"] = ((act_width,), np.float32)
            # what Ratio will owe at the first train window (the burst)
            steps_per_iter = num_envs * int(cfg.env.action_repeat) * fabric.num_processes
            burst = 1 if cfg.dry_run else Ratio(
                cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps
            )(
                max(int(cfg.algo.learning_starts) // steps_per_iter, 1)
                * steps_per_iter / fabric.world_size
            )
            with SPANS.setup_span("setup.replay"):  # the probe compile that sizes the ring, and the ring
                rb, train_phase_dev = build_device_replay(
                    fabric, cfg, capacity, num_envs, leaf_specs, _make_fused,
                    train_state=(params, opt_state) + ((sentinel.init_state(),) if sentinel is not None else ()),
                    first_window=burst,
                    batch_bytes=sampled_bytes(leaf_specs, batch_size, seq_len),
                    sequential=True, memmap_dir=memmap_dir, min_window=seq_len * 2,
                )
        else:
            with SPANS.setup_span("setup.replay"):
                rb = EnvIndependentReplayBuffer(
                    capacity,
                    n_envs=num_envs,
                    buffer_cls=SequentialReplayBuffer,
                    memmap=cfg.buffer.memmap,
                    memmap_dir=memmap_dir,
                )
    use_device_replay = isinstance(rb, DeviceReplay)
    guard_on = bool(cfg.buffer.get("transfer_guard", False)) and use_device_replay
    # a checkpoint only contains "rb" if it was saved with buffer.checkpoint
    # (or injected explicitly, e.g. P2E finetuning's load_from_exploration) —
    # so presence alone decides
    if state and state.get("rb") is not None:
        rb.load_state_dict({"buffers": state["rb"]}) if isinstance(state["rb"], list) else rb.load_state_dict(state["rb"])

    # ---------------- counters ------------------------------------------------
    # GLOBAL env-step accounting: every process steps its own envs
    policy_steps_per_iter = num_envs * int(cfg.env.action_repeat) * fabric.num_processes
    total_iters = max(int(cfg.algo.total_steps) // policy_steps_per_iter, 1)
    if cfg.dry_run:
        # dry run = collect just enough for one sequence sample (2x for the
        # EpisodeBuffer, which must first COMMIT a >=seq_len episode), then
        # ONE optimization dispatch
        total_iters = 2 * int(cfg.algo.per_rank_sequence_length) + 4
    learning_starts = int(cfg.algo.learning_starts) // policy_steps_per_iter if not cfg.dry_run else 0
    start_iter = int(state.get("update", 0)) + 1 if state else 1
    policy_step = int(state.get("policy_step", 0))
    last_log = int(state.get("last_log", 0))
    last_checkpoint = int(state.get("last_checkpoint", 0))
    grad_step_counter = int(state.get("grad_steps", 0))
    if state:
        learning_starts += start_iter

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state and "ratio" in state:
        ratio.load_state_dict(state["ratio"])
    if state and "psync" in state:
        psync.load_state_dict(state["psync"])

    # ---------------- env bookkeeping (reference: dreamer_v3.py:540-657) ----
    # rank-offset: each process's envs must be distinct streams or
    # multi-host DP collects the same data num_processes times
    with SPANS.setup_span("setup.env"):  # the envs' first reset
        obs, _ = envs.reset(seed=cfg.seed + rank * num_envs)
    step_data: Dict[str, np.ndarray] = {}
    for k in obs_keys:
        step_data[k] = np.asarray(obs[k])[None]
    step_data["rewards"] = np.zeros((1, num_envs), np.float32)
    step_data["terminated"] = np.zeros((1, num_envs), np.float32)
    step_data["truncated"] = np.zeros((1, num_envs), np.float32)
    step_data["is_first"] = np.ones((1, num_envs), np.float32)
    last_metrics = None
    counter_dev = None  # device-resident grad-step counter (zero-copy path)
    h_dev = None  # device-resident sentinel state (resilience/health.py)
    train_windows = 0  # completed dispatched windows (guards arm past warmup)
    # per-rank player key stream, advanced inside player_step; the main
    # `key` stays rank-identical for train dispatches
    player_key = jax.device_put(
        # resume this rank's player RNG stream bit-exactly when saved
        jnp.asarray(state["player_key"]) if state and state.get("player_key") is not None
        else jax.random.fold_in(key, rank),
        host,
    )

    # parallel compile warm-up: the player executable lowers+compiles in the
    # pool while this thread steps random prefill actions (XLA compilation
    # releases the GIL), so the first post-prefill policy step finds its
    # executable already built instead of stalling the rollout
    if bool(cfg.algo.get("compile_warmup", True)):
        def _warm_player(first_obs=obs):
            with jax.default_device(host):
                warm_obs = prepare_obs(first_obs, cnn_keys, mlp_keys)
                carry0 = tuple(jnp.asarray(c) for c in init_player_carry(num_envs))
                player_step.warmup(player_params, carry0, warm_obs, player_key)

        fabric.compile_pool.submit_fn(_warm_player)

    from sheeprl_tpu.utils.profiler import ProfilerGate

    profiler = ProfilerGate(cfg, log_dir)
    for update in range(start_iter, total_iters + 1):
        profiler.step(update)
        SPANS.iteration(update)  # the `iter` span: closes the one before
        policy_step += policy_steps_per_iter
        with timer("Time/env_interaction_time"):
            if update <= learning_starts and not state:
                sampled = np.stack([act_space.sample() for _ in range(num_envs)])
                env_actions = np.asarray(sampled, np.float32).reshape(num_envs, -1)
                if is_continuous:
                    actions = env_actions
                else:
                    idx = sampled.reshape(num_envs, -1)
                    parts = []
                    for b, d in enumerate(actions_dim):
                        oh = np.zeros((num_envs, d), np.float32)
                        oh[np.arange(num_envs), idx[:, b]] = 1.0
                        parts.append(oh)
                    actions = np.concatenate(parts, -1)
            else:
                with jax.default_device(host):
                    dev_obs = prepare_obs(obs, cnn_keys, mlp_keys)
                    new_carry, action_oh, player_key = player_step(
                        player_params,
                        tuple(jnp.asarray(c) for c in player_carry),
                        dev_obs,
                        player_key,
                    )
                    player_carry = tuple(np.array(c) for c in new_carry)
                    actions = np.asarray(action_oh, np.float32)
                if is_continuous:
                    env_actions = actions
                else:
                    idxs, start = [], 0
                    for d in actions_dim:
                        idxs.append(actions[:, start:start + d].argmax(-1))
                        start += d
                    env_actions = np.stack(idxs, -1).astype(np.float32)

            step_data["actions"] = actions[None]
            rb.add({k: (v[..., None] if v.ndim == 2 else v) for k, v in step_data.items()})

            next_obs, rewards, terminated, truncated, info = envs.step(
                actions_for_env(env_actions, act_space)
            )
            dones = np.logical_or(terminated, truncated)

            step_data["is_first"] = np.zeros((1, num_envs), np.float32)

            # env crashed + restarted: the stream broke — mark the last stored
            # step truncated and restart the episode bookkeeping
            # (reference: dreamer_v3.py:595-608)
            roe = info.get("restart_on_exception")
            if roe is not None:
                for i in np.nonzero(np.asarray(roe, bool))[0]:
                    if dones[i]:
                        continue
                    # the stream broke: the next stored step starts a new
                    # episode, and the buffer truncates (or drops) the
                    # partial one — see ReplayBuffer/EpisodeBuffer.repair_tail
                    step_data["is_first"][:, i] = 1.0
                    rb.repair_tail(i)

            for ep_ret, ep_len in episode_stats(info):
                aggregator.update("Rewards/rew_avg", ep_ret)
                aggregator.update("Game/ep_len_avg", ep_len)

            # real final observation of done envs
            real_next_obs = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
            done_idx = np.nonzero(dones)[0]
            if done_idx.size:
                final = final_obs_rows(info, done_idx, obs_keys)
                if final is not None:
                    for k in obs_keys:
                        real_next_obs[k][done_idx] = final[k]

            for k in obs_keys:
                step_data[k] = np.asarray(next_obs[k])[None]
            obs = next_obs
            rewards = np.asarray(rewards, np.float32)
            if cfg.env.clip_rewards:
                rewards = np.tanh(rewards)
            step_data["rewards"] = rewards[None]
            step_data["terminated"] = terminated.astype(np.float32)[None]
            step_data["truncated"] = truncated.astype(np.float32)[None]

            if done_idx.size:
                # store the final transition row for finished episodes
                # (reference: dreamer_v3.py:639-657)
                reset_data: Dict[str, np.ndarray] = {}
                for k in obs_keys:
                    reset_data[k] = real_next_obs[k][done_idx][None]
                reset_data["terminated"] = step_data["terminated"][:, done_idx, None]
                reset_data["truncated"] = step_data["truncated"][:, done_idx, None]
                reset_data["actions"] = np.zeros((1, done_idx.size, act_width), np.float32)
                reset_data["rewards"] = step_data["rewards"][:, done_idx, None]
                reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
                rb.add(reset_data, indices=done_idx.tolist())

                step_data["rewards"][:, done_idx] = 0.0
                step_data["terminated"][:, done_idx] = 0.0
                step_data["truncated"][:, done_idx] = 0.0
                step_data["is_first"][:, done_idx] = 1.0
                fresh = init_player_carry(done_idx.size)
                for c_old, c_new in zip(player_carry, fresh):
                    c_old[done_idx] = c_new

        # ---------------- training -------------------------------------------
        if isinstance(rb, EpisodeBuffer):
            can_sample = len(rb) > seq_len and len(rb.buffer) > 0
        elif use_device_replay:
            can_sample = rb.can_sample_sequences(seq_len)
        else:
            can_sample = any(len(b) > seq_len for b in rb.buffer)
        if update >= learning_starts and can_sample:
            per_rank_gradient_steps = ratio(policy_step / fabric.world_size)
            if cfg.dry_run:
                per_rank_gradient_steps = 1 if update == total_iters else 0
            if per_rank_gradient_steps > 0 and train_phase_dev is not None:
                with timer("Time/train_time"):
                    # zero-copy steady state: sequences are sampled from the
                    # HBM ring INSIDE the compiled dispatch — nothing ships
                    # H2D per update, and (optionally) the transfer guard
                    # proves it past the first (warmup) window.  Windows are
                    # still chunked into powers of two: distinct U values are
                    # distinct executables, so bursts must reuse shapes
                    # (data/device_replay.update_chunks).
                    if counter_dev is None:
                        # replicated on the mesh, matching the program's output
                        # placement — a single-device stage would cost one
                        # extra (first-window) executable on multi-device
                        counter_dev = fabric.replicate(np.int32(grad_step_counter))
                    if sentinel is not None and h_dev is None:
                        h_dev = sentinel.init_state()
                    player_params = psync.before_dispatch(player_params)
                    with steady_guard(guard_on and train_windows > 0):
                        # chunk cap honors BOTH budgets: compile reuse and the
                        # HBM bytes the gathered (U, L, B, *) block materializes
                        for u in update_chunks(
                            per_rank_gradient_steps,
                            bytes_per_update=rb.sampled_bytes_per_update(batch_size, seq_len),
                        ):
                            key, tk = jax.random.split(key)
                            if sentinel is not None:
                                params, opt_state, h_dev, counter_dev, last_metrics = (
                                    train_phase_dev(
                                        params, opt_state, h_dev, rb.buffers, rb.cursor,
                                        tk, counter_dev, n_samples=u,
                                    )
                                )
                            else:
                                params, opt_state, counter_dev, last_metrics = train_phase_dev(
                                    params, opt_state, rb.buffers, rb.cursor, tk,
                                    counter_dev, n_samples=u,
                                )
                            grad_step_counter += u
                    train_windows += 1
                    player_params = psync.after_dispatch(params, player_params)
            elif per_rank_gradient_steps > 0:
                with timer("Time/train_time"):
                    # host-numpy fallback (CPU runs, EpisodeBuffer): burst
                    # windows (the first one repays every pre-training env
                    # step at once) are chunked into powers of two so a burst
                    # reuses a handful of compiled window shapes.
                    #
                    # ONE player sync per ratio window, hoisted OUT of the
                    # chunk loop: a per-chunk refresh would pull the full
                    # player params D2H once per chunk of a burst
                    player_params = psync.before_dispatch(player_params)
                    for u in update_chunks(per_rank_gradient_steps):
                        sample = rb.sample(
                            batch_size,
                            n_samples=u,
                            sequence_length=seq_len,
                        )  # (U, L, batch, *)
                        blocks: Dict[str, jax.Array] = {}
                        for k in cnn_keys:
                            x = np.asarray(sample[k])
                            if x.ndim == 7:  # (U, L, B, S, H, W, C) framestack
                                x = merge_framestack(x)
                            # ship uint8 (4x less H2D traffic); the train phase
                            # normalizes on device
                            blocks[k] = jnp.asarray(x)
                        for k in mlp_keys:
                            x = np.asarray(sample[k], np.float32)
                            blocks[k] = jnp.asarray(x.reshape(*x.shape[:3], -1))
                        blocks["actions"] = jnp.asarray(np.asarray(sample["actions"], np.float32))
                        blocks["rewards"] = jnp.asarray(np.asarray(sample["rewards"], np.float32)[..., 0])
                        blocks["terminated"] = jnp.asarray(np.asarray(sample["terminated"], np.float32)[..., 0])
                        blocks["is_first"] = jnp.asarray(np.asarray(sample["is_first"], np.float32)[..., 0])
                        blocks = fabric.shard_batch(blocks, axis=2)
                        key, tk = jax.random.split(key)
                        if sentinel is not None:
                            if h_dev is None:
                                h_dev = sentinel.init_state()
                            h_dev, params, opt_state, last_metrics = train_phase(
                                h_dev, params, opt_state, blocks, tk,
                                jnp.int32(grad_step_counter),
                            )
                        else:
                            params, opt_state, last_metrics = train_phase(
                                params, opt_state, blocks, tk, jnp.int32(grad_step_counter)
                            )
                        grad_step_counter += u
                    player_params = psync.after_dispatch(params, player_params)

        # ---------------- training-health sentinel -----------------------------
        # per-interval host poll of the device HealthState: Health/* metrics
        # through the hub + recorder events.  The dreamer loops implement
        # rollback through the process boundary: the typed DivergenceError
        # reaches cli.run's crash path (postmortem reason surfaced) and the
        # supervisor relaunches with checkpoint.resume_from=auto — i.e.
        # rollback to the last committed snapshot.
        if (
            sentinel is not None
            and h_dev is not None
            and sentinel.should_poll(update, total_iters)
            and sentinel.poll(h_dev, policy_step) == "rollback"
        ):
            raise DivergenceError(
                f"training diverged at step {policy_step}; relaunch with "
                "checkpoint.resume_from=auto to roll back to the last committed "
                "snapshot (sheeprl-tpu-supervise does this automatically)"
            )

        # ---------------- logging ---------------------------------------------
        if cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or update == total_iters or cfg.dry_run
        ):
            if last_metrics is not None:
                wm_l, ol, rl, sl, cl, kl_, pl, vl, pe, pre = last_metrics
                aggregator.update("Loss/world_model_loss", wm_l)
                aggregator.update("Loss/observation_loss", ol)
                aggregator.update("Loss/reward_loss", rl)
                aggregator.update("Loss/state_loss", sl)
                aggregator.update("Loss/continue_loss", cl)
                aggregator.update("State/kl", kl_)
                aggregator.update("Loss/policy_loss", pl)
                aggregator.update("Loss/value_loss", vl)
                aggregator.update("State/post_entropy", pe)
                aggregator.update("State/prior_entropy", pre)
            last_log = flush_metrics(
                aggregator, timer, logger, policy_step, last_log,
                extra_metrics={
                    "Params/replay_ratio": grad_step_counter * fabric.world_size / max(policy_step, 1),
                    # deferred-sync staleness, made visible (ISSUE 12)
                    **psync.metrics(),
                },
            )

        # ---------------- checkpoint ------------------------------------------
        if ckpt_mgr.should_save(policy_step, last_checkpoint, final=update == total_iters):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": params,
                "opt_state": opt_state,
                "key": key,
                "player_key": player_key,
                "update": update,
                "policy_step": policy_step,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
                "ratio": ratio.state_dict(),
                "psync": psync.state_dict(),
                "grad_steps": grad_step_counter,
            }
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_{rank}.ckpt"),
                state=ckpt_state,
                replay_buffer=rb if cfg.buffer.checkpoint else None,
            )
        if ckpt_mgr.preempted:
            fabric.print(f"Preemption: committed checkpoint at step {policy_step}, exiting")
            break

    SPANS.end_iteration()
    profiler.close()
    envs.close()
    if sentinel is not None:
        sentinel.close()
    if getattr(rb, "spill", None) is not None:
        rb.spill.close()
    ckpt_mgr.finalize()
    if fabric.is_global_zero and cfg.algo.run_test and not ckpt_mgr.preempted:
        # the deferred-sync player may be one window stale: sync once more
        player_params = psync.init(params)
        test(player_test_step, player_params, cfg, log_dir, logger)
    if logger is not None:
        logger.close()


def make_wm_stages(cfg, world_model, cnn_keys, mlp_keys):
    """Build the world-model forward and its pipeline stage chain.

    Returns ``(wm_forward, stage_fns, stage_names)``.  Module-level (not
    nested in :func:`make_train_phase`) so that standalone per-stage
    programs (``parallel/pipeline.py compile_stage_pair``) and the
    described-TPU compile test (``tests/test_models/test_tpu_compile.py``)
    build from exactly the functions the fused train phase pipelines.
    """
    obs_keys = tuple(cnn_keys) + tuple(mlp_keys)
    stoch_flat = world_model.stoch_flat
    rec_size = cfg.algo.world_model.recurrent_model.recurrent_state_size
    wm_loss_cfg = dict(
        kl_dynamic=float(cfg.algo.world_model.kl_dynamic),
        kl_representation=float(cfg.algo.world_model.kl_representation),
        kl_free_nats=float(cfg.algo.world_model.kl_free_nats),
        kl_regularizer=float(cfg.algo.world_model.kl_regularizer),
        continue_scale_factor=float(cfg.algo.world_model.continue_scale_factor),
    )
    remat = bool(cfg.algo.get("remat", False))

    def maybe_remat(f):
        return jax.checkpoint(f) if remat else f

    pipe = resolve_pipeline(cfg)

    # The world-model forward is factored into its pipeline stage map
    # (encoder → RSSM → heads/decoder, parallel/pipeline.py): ``_encode``,
    # ``_rssm_inputs`` and ``_heads_losses`` are shared verbatim by the
    # monolithic ``wm_forward`` (pipeline off — op-for-op the pre-pipeline
    # program) and by the per-microbatch stage functions (pipeline on).  The
    # ONLY computation the two paths do differently is where posterior
    # sampling noise is drawn: ``wm_forward`` samples inside the scan at
    # batch shape (``WorldModel.dynamic``), the stages consume pre-drawn
    # full-batch noise row-sliced per microbatch
    # (``WorldModel.dynamic_noise`` — the sample-invariance law, so both
    # paths draw bit-identical posterior samples).

    def _encode(wm_params, data):
        """Stage 1 — normalize + encode: → (obs, embed (L, B, E))."""
        L, B = data["rewards"].shape
        with jax.named_scope("wm.encoder"):
            obs = normalize_obs_block(data, cnn_keys, obs_keys)
            flat_obs = {kk: v.reshape((L * B,) + v.shape[2:]) for kk, v in obs.items()}
            embed = world_model.apply(wm_params, flat_obs, method=WorldModel.encode)
            return obs, embed.reshape(L, B, -1)

    def _rssm_inputs(data):
        # shifted actions: h_t consumes a_{t-1} (reference: dreamer_v3.py:105)
        actions = jnp.concatenate(
            [jnp.zeros_like(data["actions"][:1]), data["actions"][:-1]], axis=0
        )
        is_first = data["is_first"].at[0].set(1.0)[..., None]
        return actions, is_first

    @jax.named_scope("wm.heads")
    def _heads_losses(wm_params, data, obs, latents, post_logits, prior_logits):
        """Stage 3 — decoder/reward/continue heads + world-model loss."""
        L, B = data["rewards"].shape
        flat_latents = latents.reshape(L * B, -1)

        recon = world_model.apply(wm_params, flat_latents, method=WorldModel.decode)
        obs_log_probs = {}
        for kk in cnn_keys:
            dist = MSEDistribution(recon[kk].reshape(obs[kk].shape), event_dims=3)
            obs_log_probs[kk] = dist.log_prob(obs[kk])
        for kk in mlp_keys:
            dist = SymlogDistribution(recon[kk].reshape(L, B, -1), event_dims=1)
            obs_log_probs[kk] = dist.log_prob(obs[kk])

        reward_logits = world_model.apply(wm_params, flat_latents, method=WorldModel.reward_logits)
        pr = TwoHotEncodingDistribution(reward_logits.reshape(L, B, -1), dims=1)
        reward_lp = pr.log_prob(data["rewards"][..., None])

        cont_logits = world_model.apply(wm_params, flat_latents, method=WorldModel.continue_logits)
        pc = Bernoulli(cont_logits.reshape(L, B), event_dims=0)
        cont_lp = pc.log_prob(1.0 - data["terminated"])

        loss, aux = world_model_loss(
            obs_log_probs, reward_lp, cont_lp, post_logits, prior_logits, **wm_loss_cfg
        )
        aux["latents"] = latents
        aux["post_logits"] = post_logits
        aux["prior_logits"] = prior_logits
        return loss, aux

    def wm_forward(wm_params, data, k):
        """Encoder + RSSM scan + heads → loss and latents for behavior."""
        L, B = data["rewards"].shape
        obs, embed = _encode(wm_params, data)
        actions, is_first = _rssm_inputs(data)

        with jax.named_scope("wm.rssm"):
            h0 = jnp.zeros((B, rec_size))
            z0 = jnp.zeros((B, stoch_flat))

            keys = jax.random.split(k, L)
            if world_model.decoupled_rssm:
                # DecoupledRSSM: ALL posteriors computed and sampled in one
                # batched pass (no h dependence); only the GRU+prior stay in the
                # scan — a much lighter sequential step on TPU
                post_logits = world_model.apply(
                    wm_params, embed.reshape(L * B, -1), method=WorldModel.posterior_decoupled
                ).reshape(L, B, world_model.stochastic_size, world_model.discrete_size)
                zs = jax.vmap(
                    lambda lg, kk: OneHotCategorical(lg, unimix=world_model.unimix).rsample(kk)
                )(post_logits, keys).reshape(L, B, stoch_flat)
                prev_zs = jnp.concatenate([jnp.zeros_like(zs[:1]), zs[:-1]], 0)

                def step(h, xs):
                    prev_z, act_t, first_t = xs
                    h, prior_logits = world_model.apply(
                        wm_params, h, prev_z, act_t, first_t, method=WorldModel.recurrent_prior
                    )
                    return h, (h, prior_logits)

                _, (hs, prior_logits) = jax.lax.scan(maybe_remat(step), h0, (prev_zs, actions, is_first))
            else:
                def step(carry, xs):
                    h, z = carry
                    embed_t, act_t, first_t, k_t = xs
                    h, z, post_logits, prior_logits = world_model.apply(
                        wm_params, h, z, act_t, embed_t, first_t, k_t, method=WorldModel.dynamic
                    )
                    return (h, z), (h, z, post_logits, prior_logits)

                _, (hs, zs, post_logits, prior_logits) = jax.lax.scan(
                    maybe_remat(step), (h0, z0), (embed, actions, is_first, keys)
                )
            latents = jnp.concatenate([zs, hs], -1)  # (L, B, stoch+rec)
        return _heads_losses(wm_params, data, obs, latents, post_logits, prior_logits)

    # ---- pipeline stage functions (parallel/pipeline.py chain shapes) ----
    # const per microbatch: {"data": dict of (L, b, *), "noise": (L, b, S, D)}

    def _enc_stage(wm_params, _carry, const):
        _, embed = _encode(wm_params, const["data"])
        return embed

    @jax.named_scope("wm.rssm")
    def _rssm_stage(wm_params, embed, const):
        data, noise = const["data"], const["noise"]
        L, B = data["rewards"].shape
        actions, is_first = _rssm_inputs(data)
        h0 = jnp.zeros((B, rec_size))
        z0 = jnp.zeros((B, stoch_flat))
        if world_model.decoupled_rssm:
            post_logits = world_model.apply(
                wm_params, embed.reshape(L * B, -1), method=WorldModel.posterior_decoupled
            ).reshape(L, B, world_model.stochastic_size, world_model.discrete_size)
            zs = jax.vmap(
                lambda lg, nz: OneHotCategorical(
                    lg, unimix=world_model.unimix
                ).rsample_from_noise(nz)
            )(post_logits, noise).reshape(L, B, stoch_flat)
            prev_zs = jnp.concatenate([jnp.zeros_like(zs[:1]), zs[:-1]], 0)

            def step(h, xs):
                prev_z, act_t, first_t = xs
                h, prior_logits = world_model.apply(
                    wm_params, h, prev_z, act_t, first_t, method=WorldModel.recurrent_prior
                )
                return h, (h, prior_logits)

            _, (hs, prior_logits) = jax.lax.scan(maybe_remat(step), h0, (prev_zs, actions, is_first))
        else:
            def step(carry, xs):
                h, z = carry
                embed_t, act_t, first_t, nz_t = xs
                h, z, post_logits, prior_logits = world_model.apply(
                    wm_params, h, z, act_t, embed_t, first_t, nz_t,
                    method=WorldModel.dynamic_noise,
                )
                return (h, z), (h, z, post_logits, prior_logits)

            _, (hs, zs, post_logits, prior_logits) = jax.lax.scan(
                maybe_remat(step), (h0, z0), (embed, actions, is_first, noise)
            )
        latents = jnp.concatenate([zs, hs], -1)
        return latents, post_logits, prior_logits

    def _heads_stage(wm_params, carry, const):
        latents, post_logits, prior_logits = carry
        data = const["data"]
        # obs recomputed from the const slice (cheap normalize) instead of
        # carried from stage 1: keeps the stage chain linear — no
        # encoder→heads skip buffer alive across the whole 1F1B window
        obs = normalize_obs_block(data, cnn_keys, obs_keys)
        return _heads_losses(wm_params, data, obs, latents, post_logits, prior_logits)

    # stage grouping: the dreamer stage map has 3 units; pipeline.stages
    # picks how they fuse onto mesh sub-groups (docs/pipeline.md)
    if pipe.stages >= 3:
        if pipe.stages > 3:
            raise ValueError(
                f"pipeline.stages={pipe.stages}: the dreamer_v3 stage map has "
                "3 units (encoder, rssm, heads) — use stages in {1, 2, 3}"
            )
        stage_fns = (_enc_stage, _rssm_stage, _heads_stage)
        stage_names = ("encoder", "rssm", "heads")
    elif pipe.stages == 2:
        def _enc_rssm_stage(wm_params, _carry, const):
            embed = _enc_stage(wm_params, None, const)
            return _rssm_stage(wm_params, embed, const)

        stage_fns = (_enc_rssm_stage, _heads_stage)
        stage_names = ("encoder_rssm", "heads")
    else:
        def _wm_stage(wm_params, _carry, const):
            embed = _enc_stage(wm_params, None, const)
            carry = _rssm_stage(wm_params, embed, const)
            return _heads_stage(wm_params, carry, const)

        stage_fns = (_wm_stage,)
        stage_names = ("world_model",)

    return wm_forward, stage_fns, stage_names


def make_train_phase(
    fabric, cfg, world_model, actor, critic, wm_opt, actor_opt, critic_opt,
    cnn_keys, mlp_keys, is_continuous, params=None, opt_state=None,
):
    """Build the jitted multi-update train phase (shared with
    __graft_entry__.py and the mesh tests so the program they check IS the
    training program).

    ``params``/``opt_state``: the already-placed state trees.  When given,
    their partition-rules shardings are pinned as the program's in/out
    shardings (``compile.state_io_shardings``) — combined with the argnum
    0/1 donation this guarantees the optimizer state stays sharded exactly
    like its params and both are updated in place across every window."""
    stoch_flat = world_model.stoch_flat
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    tau = float(cfg.algo.critic.tau)
    target_freq = int(cfg.algo.critic.per_rank_target_network_update_freq)
    ent_coef = float(cfg.algo.actor.ent_coef)
    moments_cfg = cfg.algo.actor.moments
    # algo.remat: rematerialize the sequential scan bodies on the backward
    # pass (jax.checkpoint) — trades ~1 extra forward of the cell for not
    # storing L (resp. horizon) copies of its intermediates in HBM, the
    # standard lever for fitting bigger batches/sizes on-chip
    remat = bool(cfg.algo.get("remat", False))

    def maybe_remat(f):
        return jax.checkpoint(f) if remat else f

    # pipeline.* group: stage split + 1F1B microbatch schedule for the
    # world-model update, row-chunking for the imagination head evals
    # (parallel/pipeline.py, docs/pipeline.md); the disabled spec keeps the
    # monolithic pre-pipeline program op-for-op
    pipe = resolve_pipeline(cfg)
    pipe.check_algo(cfg.algo.name)
    imag_chunks = pipe.imagination_microbatches

    wm_forward, stage_fns, stage_names = make_wm_stages(
        cfg, world_model, cnn_keys, mlp_keys
    )

    if pipe.enabled:
        s_z, d_z = world_model.stochastic_size, world_model.discrete_size
        batch_aux = ("latents", "post_logits", "prior_logits")
        constrain = stage_batch_constraint(fabric.mesh, fabric.data_axis, batch_axis=1)

        def wm_value_and_grad(wm_params, data, k_wm):
            L, B = data["rewards"].shape
            keys = jax.random.split(k_wm, L)
            # full-batch noise with the baseline's exact per-timestep keys;
            # microbatch slices then sample the exact bits wm_forward would
            noise = jax.vmap(
                lambda kk: OneHotCategorical.sample_noise(kk, (B, s_z, d_z))
            )(keys)
            consts = split_microbatches(
                {"data": data, "noise": noise}, pipe.microbatches, axis=1
            )
            loss, aux_m, grads = pipeline_value_and_grad(
                stage_fns, wm_params, consts,
                microbatches=pipe.microbatches, stage_names=stage_names,
                constrain=constrain,
            )
            # reassemble: batch-shaped aux un-microbatches to (L, B, *);
            # per-microbatch scalar means average to the batch mean
            aux = {
                kk: merge_microbatches(v, axis=1) if kk in batch_aux else v.mean(0)
                for kk, v in aux_m.items()
            }
            return (loss, aux), grads
    else:
        def wm_value_and_grad(wm_params, data, k_wm):
            return jax.value_and_grad(wm_forward, has_aux=True)(wm_params, data, k_wm)

    def behavior_update(p, o_state, moments, latents, terminated, k):
        """Imagination rollout + actor and critic updates."""
        L, B = terminated.shape
        n = L * B
        start_latents = jax.lax.stop_gradient(latents.reshape(1, n, -1))[0]

        @jax.named_scope("actor.loss")
        def actor_loss_fn(actor_params):
            def img_step(carry, k_t):
                h, z = carry
                latent = jnp.concatenate([z, h], -1)
                k_a, k_z = jax.random.split(k_t)
                head = actor.apply(actor_params, jax.lax.stop_gradient(latent))
                action = actor.sample(head, k_a)
                h, z = world_model.apply(
                    p["world_model"], h, z, action, k_z, method=WorldModel.imagination
                )
                return (h, z), (latent, action)

            h0 = start_latents[:, stoch_flat:]
            z0 = start_latents[:, :stoch_flat]
            keys = jax.random.split(k, horizon + 1)
            # H+1 scan steps emit the pre-action latent each time → traj holds
            # states z0, z'1, ..., z'H (reference diagram, dreamer_v3.py:222-232)
            with jax.named_scope("behavior.imagine"):
                _, (traj, actions_seq) = jax.lax.scan(maybe_remat(img_step), (h0, z0), keys)
            # predictions over the whole imagined trajectory
            # the imagination batch's wide head evals, row-chunked under
            # pipeline.imagination_microbatches (chunked_rows is fn(x)
            # verbatim at 1 — per-row values are unchanged either way)
            flat_traj = traj.reshape((horizon + 1) * n, -1)
            rewards = TwoHotEncodingDistribution(
                chunked_rows(
                    lambda x: world_model.apply(
                        p["world_model"], x, method=WorldModel.reward_logits
                    ),
                    flat_traj, imag_chunks,
                ).reshape(horizon + 1, n, -1),
                dims=1,
            ).mean[..., 0]
            values = TwoHotEncodingDistribution(
                chunked_rows(
                    lambda x: critic.apply(p["critic"], x), flat_traj, imag_chunks
                ).reshape(horizon + 1, n, -1),
                dims=1,
            ).mean[..., 0]
            continues = Bernoulli(
                chunked_rows(
                    lambda x: world_model.apply(
                        p["world_model"], x, method=WorldModel.continue_logits
                    ),
                    flat_traj, imag_chunks,
                ).reshape(horizon + 1, n)
            ).mode()
            true_continue = (1.0 - terminated).reshape(1, n)
            continues = jnp.concatenate([true_continue, continues[1:]], 0)

            lambda_values = compute_lambda_values(
                rewards[1:], values[1:], continues[1:] * gamma, lmbda
            )  # (H, n)
            discount = jnp.cumprod(continues * gamma, axis=0) / gamma  # (H+1, n)
            discount = jax.lax.stop_gradient(discount)

            new_moments, offset, invscale = moments_update(
                moments, lambda_values,
                decay=float(moments_cfg.decay), max_=float(moments_cfg.max),
                plow=float(moments_cfg.percentile.low), phigh=float(moments_cfg.percentile.high),
            )
            baseline = values[:-1]
            normed_lambda = (lambda_values - offset) / invscale
            normed_baseline = (baseline - offset) / invscale
            advantage = normed_lambda - normed_baseline  # (H, n)

            heads = actor.apply(actor_params, jax.lax.stop_gradient(traj))
            if is_continuous:
                objective = advantage
            else:
                lp = actor.log_prob(heads[:-1], jax.lax.stop_gradient(actions_seq[:-1]))
                objective = lp * jax.lax.stop_gradient(advantage)
            entropy = actor.entropy(heads[:-1])
            policy_loss = -jnp.mean(discount[:-1] * (objective + ent_coef * entropy))
            return policy_loss, (traj, lambda_values, discount)

        (pl, (traj, lambda_values, discount)), a_grads = jax.value_and_grad(
            actor_loss_fn, has_aux=True
        )(p["actor"])
        with jax.named_scope("actor.optim"):
            a_updates, new_a_opt = actor_opt.update(a_grads, o_state["actor"], p["actor"])
            p = {**p, "actor": optax.apply_updates(p["actor"], a_updates)}

        # recompute moments state outside the grad fn (pure duplicate, cheap)
        new_moments, _, _ = moments_update(
            moments, lambda_values,
            decay=float(moments_cfg.decay), max_=float(moments_cfg.max),
            plow=float(moments_cfg.percentile.low), phigh=float(moments_cfg.percentile.high),
        )

        # ---- critic (Eq. 10): two-hot NLL of λ-returns + target regularizer
        traj_sg = jax.lax.stop_gradient(traj[:-1])
        flat_sg = traj_sg.reshape(horizon * traj_sg.shape[1], -1)
        with jax.named_scope("critic.loss"):
            target_mean = TwoHotEncodingDistribution(
                chunked_rows(
                    lambda x: critic.apply(p["target_critic"], x), flat_sg, imag_chunks
                ).reshape(horizon, -1, cfg.algo.critic.bins),
                dims=1,
            ).mean

        @jax.named_scope("critic.loss")
        def critic_loss_fn(critic_params):
            qv = TwoHotEncodingDistribution(
                chunked_rows(
                    lambda x: critic.apply(critic_params, x), flat_sg, imag_chunks
                ).reshape(horizon, -1, cfg.algo.critic.bins),
                dims=1,
            )
            vl = -qv.log_prob(jax.lax.stop_gradient(lambda_values)[..., None])
            vl = vl - qv.log_prob(jax.lax.stop_gradient(target_mean))
            return jnp.mean(vl * discount[:-1])

        vl, c_grads = jax.value_and_grad(critic_loss_fn)(p["critic"])
        with jax.named_scope("critic.optim"):
            c_updates, new_c_opt = critic_opt.update(c_grads, o_state["critic"], p["critic"])
            p = {**p, "critic": optax.apply_updates(p["critic"], c_updates)}
        o_state = {**o_state, "actor": new_a_opt, "critic": new_c_opt}
        return p, o_state, new_moments, pl, vl

    def single_update(carry, inputs):
        p, o_state, counter = carry
        data, k = inputs  # data: dict of (L, B, *)
        k_wm, k_beh = jax.random.split(k)

        (wm_l, aux), wm_grads = wm_value_and_grad(p["world_model"], data, k_wm)
        with jax.named_scope("wm.optim"):
            wm_updates, new_wm_opt = wm_opt.update(wm_grads, o_state["world_model"], p["world_model"])
            p = {**p, "world_model": optax.apply_updates(p["world_model"], wm_updates)}
        o_state = {**o_state, "world_model": new_wm_opt}

        p, o_state, new_moments, pl, vl = behavior_update(
            p, o_state, p["moments"], aux["latents"], data["terminated"], k_beh
        )
        p = {**p, "moments": new_moments}

        # target critic EMA (reference: dreamer_v3.py:674-680)
        with jax.named_scope("critic.optim"):
            do_ema = (counter % target_freq) == 0
            new_target = jax.tree.map(
                lambda t, o: (1 - tau) * t + tau * o, p["target_critic"], p["critic"]
            )
            p = {
                **p,
                "target_critic": jax.tree.map(
                    lambda n_, o_: jnp.where(do_ema, n_, o_), new_target, p["target_critic"]
                ),
            }

        post_ent = OneHotCategorical(jax.lax.stop_gradient(aux["post_logits"])).entropy().sum(-1).mean()
        prior_ent = OneHotCategorical(jax.lax.stop_gradient(aux["prior_logits"])).entropy().sum(-1).mean()
        metrics = (
            wm_l, aux["observation_loss"], aux["reward_loss"], aux["kl_loss"],
            aux["continue_loss"], aux["kl"], pl, vl, post_ent, prior_ent,
        )
        return (p, o_state, counter + 1), metrics

    def train_phase(p, o_state, blocks, k, counter0):
        U = blocks["rewards"].shape[0]
        keys = jax.random.split(k, U)
        (p, o_state, _), metrics = window_scan(
            single_update, (p, o_state, counter0), (blocks, keys), unroll=bool(cnn_keys)
        )
        return p, o_state, jax.tree.map(lambda x: x.mean(), metrics)

    in_sh = out_sh = None
    if params is not None and opt_state is not None:
        from sheeprl_tpu.parallel.compile import state_io_shardings
        from sheeprl_tpu.parallel.sharding import shardings_of

        # train_phase(p, o_state, blocks, k, counter0) -> (p, o_state, metrics)
        in_sh, out_sh = state_io_shardings(
            shardings_of(params), shardings_of(opt_state), n_extra_in=3, n_extra_out=1
        )
    return fabric.compile(
        train_phase,
        name=f"{cfg.algo.name}.train_phase",
        donate_argnums=(0, 1),
        in_shardings=in_sh,
        out_shardings=out_sh,
        max_recompiles=cfg.algo.get("max_recompiles"),
    )
