"""SAC, coupled topology (off-policy path of the build plan, SURVEY.md §7.4).

Capability parity with the reference train script
(reference: sheeprl/algos/sac/sac.py:81-427): uniform replay, twin-Q with
EMA targets, squashed-Gaussian actor, automatic temperature tuning with the
α-gradient synchronized across the world (reference: sac.py:68-73 — here the
mean over the globally-sharded batch does it), ``Ratio``-governed gradient
steps per env step, learning_starts prefill with random actions.

TPU-native structure:
* host player selects actions (CPU copy of actor params, refreshed after
  each train dispatch);
* each iteration's gradient steps run as ONE jitted dispatch — the replay
  batch block for ALL steps of the window is sampled host-side in one call
  (n_samples × batch, the reference's own bulk pattern,
  reference: dreamer_v3.py:664-671) and scanned over on device;
* actions live in the actor's tanh space [-1, 1] inside the framework and
  are rescaled to env bounds only at the env boundary.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.sac.agent import build_agent, ema_update, sample_action
from sheeprl_tpu.algos.sac.loss import actor_loss, alpha_loss, critic_loss
from sheeprl_tpu.algos.sac.utils import prepare_obs, test
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.device_replay import (
    build_device_replay,
    fused_uniform_train,
    resolve_device_replay,
    sampled_bytes,
    steady_guard,
    update_chunks,
)
from sheeprl_tpu.checkpoint.rollback import rollback_state
from sheeprl_tpu.parallel.compile import compile_once
from sheeprl_tpu.parallel.fabric import PlayerSync
from sheeprl_tpu.resilience.health import DivergenceError, HealthSentinel
from sheeprl_tpu.telemetry.spans import SPANS
from sheeprl_tpu.utils.env import episode_stats, final_obs_rows, make_env, vectorize
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, flush_metrics
from sheeprl_tpu.utils.optim import build_optimizer
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import Ratio, save_configs, TrainWindow, window_scan


@register_algorithm()
def main(fabric: Any, cfg: Any) -> None:
    from sheeprl_tpu.algos.sac.agent import build_agent as sac_build_agent

    def plain_apply(critic, cp, o, a, k):
        return critic.apply(cp, o, a)

    sac_loop(fabric, cfg, sac_build_agent, plain_apply)


def make_sac_train_fns(actor, critic, critic_apply, actor_opt, critic_opt, alpha_opt, cfg, act_dim):
    """The jitted SAC programs (act + scanned multi-update train phase),
    shared by the coupled loop, DroQ, and the dedicated cross-process
    decoupled topology (reference: the train() shared between
    sheeprl/algos/sac/sac.py:30-79 and sac_decoupled.py's trainer)."""
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)
    target_entropy = -float(act_dim)
    target_freq = int(cfg.algo.critic.target_network_frequency)

    def act_fn(p, obs, k, greedy=False):
        # key advances INSIDE the jitted step (one host dispatch per env
        # step instead of three; callers thread the returned key)
        k_sample, k_next = jax.random.split(k)
        a, _ = sample_action(actor, p, obs, k_sample, greedy=greedy)
        return a, k_next

    # compile-once routing (parallel/compile.py): AOT-compiled per abstract
    # signature, counted by the recompile detector
    act_fn = compile_once(
        act_fn,
        name=f"{cfg.algo.name}.act_fn",
        static_argnames=("greedy",),
        max_recompiles=cfg.algo.get("max_recompiles"),
    )

    def one_update(carry, batch_and_key):
        p, o_state, step_idx = carry
        batch, k = batch_and_key
        k_next, k_pi, k_d1, k_d2, k_d3 = jax.random.split(k, 5)
        alpha = jnp.exp(p["log_alpha"])

        # -- critic
        next_a, next_lp = sample_action(actor, p["actor"], batch["next_obs"], k_next)
        target_qs = critic_apply(critic, p["target_critic"], batch["next_obs"], next_a, k_d1)
        target_v = jnp.min(target_qs, axis=0) - alpha * next_lp
        # bootstrap THROUGH time-limit truncation: only true termination cuts
        # the return (reference: sac.py:46 uses data["terminated"])
        y = batch["rewards"] + gamma * (1.0 - batch["terminated"]) * target_v

        def c_loss(cp):
            qs = critic_apply(critic, cp, batch["obs"], batch["actions"], k_d2)
            return critic_loss(qs, jax.lax.stop_gradient(y))

        vl, c_grads = jax.value_and_grad(c_loss)(p["critic"])
        c_updates, new_c_opt = critic_opt.update(c_grads, o_state["critic"], p["critic"])
        p = {**p, "critic": optax.apply_updates(p["critic"], c_updates)}

        # -- actor
        def a_loss(ap):
            a, lp = sample_action(actor, ap, batch["obs"], k_pi)
            qs = critic_apply(critic, p["critic"], batch["obs"], a, k_d3)
            return actor_loss(alpha, lp, jnp.min(qs, axis=0)), lp

        (pl, lp), a_grads = jax.value_and_grad(a_loss, has_aux=True)(p["actor"])
        a_updates, new_a_opt = actor_opt.update(a_grads, o_state["actor"], p["actor"])
        p = {**p, "actor": optax.apply_updates(p["actor"], a_updates)}

        # -- temperature
        def t_loss(la):
            return alpha_loss(la, lp, target_entropy)

        al, t_grads = jax.value_and_grad(t_loss)(p["log_alpha"])
        t_updates, new_t_opt = alpha_opt.update(t_grads, o_state["alpha"], p["log_alpha"])
        p = {**p, "log_alpha": p["log_alpha"] + t_updates}

        # -- EMA target (every target_network_frequency updates,
        #    reference: sac.py target update cadence)
        do_ema = (step_idx % target_freq) == 0
        new_target = ema_update(p["target_critic"], p["critic"], tau)
        p = {
            **p,
            "target_critic": jax.tree.map(
                lambda n, o: jnp.where(do_ema, n, o), new_target, p["target_critic"]
            ),
        }
        o_state = {"actor": new_a_opt, "critic": new_c_opt, "alpha": new_t_opt}
        return (p, o_state, step_idx + 1), (vl, pl, al)

    def train_phase(p, o_state, batches, k, step0):
        """``batches``: dict of (U, batch, ...) stacked update blocks."""
        U = batches["rewards"].shape[0]
        keys = jax.random.split(k, U)
        # conv-free matmul body: scan carries no XLA-CPU penalty here, and
        # SAC windows can be long — keep the compact lowering
        (p, o_state, _), losses = window_scan(
            one_update, (p, o_state, step0), (batches, keys), unroll=False
        )
        return p, o_state, jax.tree.map(lambda x: x.mean(), losses)

    train_phase = compile_once(
        train_phase,
        name=f"{cfg.algo.name}.train_phase",
        donate_argnums=(0, 1),
        max_recompiles=cfg.algo.get("max_recompiles"),
    )
    return act_fn, train_phase


def sac_loop(fabric: Any, cfg: Any, build_agent_fn: Any, critic_apply: Any) -> None:
    """The SAC training engine, shared with DroQ (which injects a
    dropout-active critic apply) — mirroring how the reference derives DroQ
    from SAC (reference: sheeprl/algos/droq/droq.py)."""
    rank = fabric.global_rank
    key = fabric.seed_everything(cfg.seed)

    log_dir = get_log_dir(fabric, cfg.root_dir, cfg.run_name, base=cfg.get("log_dir", "logs/runs"))
    logger = get_logger(fabric, cfg, log_dir)
    ckpt_mgr = fabric.get_checkpoint_manager(cfg, log_dir)
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    num_envs = cfg.env.num_envs
    envs = vectorize(
        cfg,
        [
            make_env(cfg, cfg.seed + rank * num_envs + i, rank, run_name=log_dir, vector_env_idx=i)
            for i in range(num_envs)
        ],
    )
    act_space = envs.single_action_space
    if not isinstance(act_space, gym.spaces.Box):
        raise ValueError("SAC supports continuous (Box) action spaces only, like the reference")
    obs_space = envs.single_observation_space
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    for k in mlp_keys:
        if k not in obs_space.spaces:
            raise ValueError(f"mlp key '{k}' not in observation space {list(obs_space.spaces)}")
    obs_dim = int(sum(np.prod(obs_space[k].shape) for k in mlp_keys))
    act_dim = int(np.prod(act_space.shape))
    act_low = np.asarray(act_space.low, np.float32)
    act_high = np.asarray(act_space.high, np.float32)

    def to_env_actions(a: np.ndarray) -> np.ndarray:
        return act_low + (a + 1.0) * 0.5 * (act_high - act_low)

    # ---------------- agent -------------------------------------------------
    state: Dict[str, Any] = {}
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)
    if state and state.get("key") is not None:
        # resume the train-dispatch RNG stream bit-exactly (rank-identical)
        key = jnp.asarray(state["key"])
    actor, critic, params = build_agent_fn(fabric, act_dim, cfg, obs_dim, state.get("agent"))

    actor_opt = build_optimizer(cfg.algo.actor.optimizer)
    critic_opt = build_optimizer(cfg.algo.critic.optimizer)
    alpha_opt = build_optimizer(cfg.algo.alpha.optimizer)
    opt_state = fabric.replicate(
        state.get("opt_state")
        or {
            "actor": actor_opt.init(params["actor"]),
            "critic": critic_opt.init(params["critic"]),
            "alpha": alpha_opt.init(params["log_alpha"]),
        }
    )

    aggregator = MetricAggregator(
        cfg.metric.aggregator.metrics if cfg.metric.log_level > 0 else {}
    )
    timer.configure(cfg.metric)

    psync = PlayerSync(fabric, cfg, extract=lambda p: p["actor"], params=params)
    host = psync.device  # single resolution of algo.player.device
    act_fn, train_phase = make_sac_train_fns(
        actor, critic, critic_apply, actor_opt, critic_opt, alpha_opt, cfg, act_dim
    )
    # training-health sentinels (resilience/health.py): the guarded program
    # wraps the compiled phase (it inlines under the trace, like the fused
    # replay programs) and threads the tiny device HealthState first —
    # health.enabled=false compiles the guard OUT and every call site below
    # keeps the exact unguarded program
    sentinel = HealthSentinel.from_config(cfg, fabric)
    if sentinel is not None:
        sentinel.register()
        train_phase = compile_once(
            sentinel.wrap(train_phase),
            name=f"{cfg.algo.name}.train_phase_guarded",
            donate_argnums=(0, 1, 2),
            max_recompiles=cfg.algo.get("max_recompiles"),
        )
    player_params = psync.init(params)

    # ---------------- counters ----------------------------------------------
    # GLOBAL env-step accounting: every process steps its own envs
    policy_steps_per_iter = num_envs * fabric.num_processes
    total_iters = max(int(cfg.algo.total_steps) // policy_steps_per_iter, 1)
    if cfg.dry_run:
        total_iters = 1
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
    window = TrainWindow(
        cfg.algo.get("train_window_iters", 1),
        pending=int(state.get("pending_gradient_steps", 0)) if state else 0,
    )
    if state and "psync" in state:
        psync.load_state_dict(state["psync"])

    # ---------------- replay: device-resident HBM ring or host numpy --------
    capacity = int(cfg.buffer.size) // num_envs
    memmap_dir = os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None
    use_device_replay = resolve_device_replay(cfg, fabric.accelerator)
    batch_size = int(cfg.algo.per_rank_batch_size) * fabric.local_world_size
    # on-device sampling folded INTO the compiled update (zero H2D in steady
    # state — data/device_replay.py): the fused program draws indices,
    # gathers, and runs the scanned multi-update phase in one dispatch
    train_phase_dev = None
    if use_device_replay:
        def _prep_batch(b):
            return {
                "obs": b["obs"],
                "next_obs": b["next_obs"],
                "actions": b["actions"],
                "rewards": b["rewards"][..., 0],
                "terminated": b["terminated"][..., 0],
            }

        def _make_fused(ring):
            return fused_uniform_train(
                fabric,
                train_phase,
                ring,
                batch_size,
                _prep_batch,
                name=f"{cfg.algo.name}.train_phase_device",
                max_recompiles=cfg.algo.get("max_recompiles"),
                health=sentinel is not None,
            )

        # the ring's rows, exactly as the loop below stores them
        obs_dim = int(sum(int(np.prod(obs_space[k].shape)) for k in mlp_keys))
        leaf_specs = {
            "obs": ((obs_dim,), np.float32),
            "next_obs": ((obs_dim,), np.float32),
            "actions": ((act_dim,), np.float32),
            "rewards": ((1,), np.float32),
            "terminated": ((1,), np.float32),
        }
        # what Ratio will owe at the first train window (the burst)
        burst = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)(
            max(learning_starts, 1) * policy_steps_per_iter / fabric.world_size
        )
        rb, train_phase_dev = build_device_replay(
            fabric, cfg, capacity, num_envs, leaf_specs, _make_fused,
            train_state=(params, opt_state) + ((sentinel.init_state(),) if sentinel is not None else ()),
            first_window=burst, batch_bytes=sampled_bytes(leaf_specs, batch_size),
            memmap_dir=memmap_dir,
        )
    else:
        rb = ReplayBuffer(capacity, num_envs, memmap=cfg.buffer.memmap, memmap_dir=memmap_dir)
    if state and cfg.buffer.checkpoint and "rb" in state:
        rb.load_state_dict(state["rb"])

    guard_on = bool(cfg.buffer.get("transfer_guard", False)) and use_device_replay

    # ---------------- main loop ---------------------------------------------
    # rank-offset: each process's envs must be distinct streams or
    # multi-host DP collects the same data num_processes times
    obs, _ = envs.reset(seed=cfg.seed + rank * num_envs)
    obs_vec = np.asarray(prepare_obs(obs, mlp_keys))
    last_losses = None
    counter_dev = None  # device-resident grad-step counter (zero-copy path)
    h_dev = None  # device-resident sentinel state (resilience/health.py)
    train_windows = 0  # completed dispatched windows (guards arm past warmup)
    # per-rank player key stream, advanced inside act_fn; the main `key`
    # stays rank-identical for train dispatches
    player_key = jax.device_put(
        # resume this rank's player RNG stream bit-exactly when saved
        jnp.asarray(state["player_key"]) if state and state.get("player_key") is not None
        else jax.random.fold_in(key, rank),
        host,
    )

    from sheeprl_tpu.utils.profiler import ProfilerGate

    profiler = ProfilerGate(cfg, log_dir)
    for update in range(start_iter, total_iters + 1):
        profiler.step(update)
        SPANS.iteration(update)  # the `iter` span: closes the one before
        policy_step += num_envs * fabric.num_processes
        with timer("Time/env_interaction_time"):
            if update <= learning_starts and not state:
                env_actions = np.stack([act_space.sample() for _ in range(num_envs)])
                span = act_high - act_low
                actions = np.clip(2.0 * (env_actions - act_low) / np.where(span == 0, 1, span) - 1.0, -1, 1)
            else:
                with jax.default_device(host):
                    a, player_key = act_fn(player_params, jnp.asarray(obs_vec), player_key)
                    actions = np.asarray(a)
                env_actions = to_env_actions(actions)
            next_obs, rewards, terminated, truncated, info = envs.step(env_actions)
            dones = np.logical_or(terminated, truncated).astype(np.float32)
            rewards = np.asarray(rewards, np.float32)

            next_vec = np.asarray(prepare_obs(next_obs, mlp_keys))
            # real next obs for done envs (autoreset replaced them)
            store_next = next_vec
            done_idx = np.nonzero(dones)[0]
            if done_idx.size:
                final = final_obs_rows(info, done_idx, mlp_keys)
                if final is not None:
                    store_next = next_vec.copy()
                    store_next[done_idx] = np.concatenate(
                        [np.asarray(final[k], np.float32).reshape(done_idx.size, -1) for k in mlp_keys],
                        axis=-1,
                    )

            rb.add(
                {
                    "obs": obs_vec[None],
                    "next_obs": store_next[None],
                    "actions": actions[None].astype(np.float32),
                    "rewards": rewards[None, :, None],
                    "terminated": terminated.astype(np.float32)[None, :, None],
                }
            )
            obs_vec = next_vec
            for ep_ret, ep_len in episode_stats(info):
                aggregator.update("Rewards/rew_avg", ep_ret)
                aggregator.update("Game/ep_len_avg", ep_len)

        # ---------------- training ------------------------------------------
        # train_window_iters K > 1 accrues the Ratio-owed gradient steps over
        # K env iterations and runs them as ONE scanned dispatch: identical
        # update math and count, the per-dispatch fixed cost (host sample,
        # transfer, launch) amortized K-fold.  Data staleness within a window is at most K-1
        # env iterations — the same staleness class as the reference's
        # decoupled trainer (reference: sheeprl/algos/sac/sac_decoupled.py).
        # K = 1 (default) is the reference-coupled cadence, bit-for-bit.
        if update >= learning_starts:
            due = window.push(
                ratio(policy_step / fabric.world_size), update, learning_starts, total_iters
            )
            if due > 0 and train_phase_dev is not None:
                with timer("Time/train_time"):
                    # zero-copy steady state: the batch never exists on the
                    # host — sampling + gather are compiled into the update
                    # dispatch, the step counter rides through the program as
                    # device data, and (optionally) the transfer guard proves
                    # no implicit H2D happens past the first (warmup) window
                    if counter_dev is None:
                        # replicated on the mesh, matching the program's output
                        # placement — a single-device stage would cost one
                        # extra (first-window) executable on multi-device
                        counter_dev = fabric.replicate(np.int32(grad_step_counter))
                    if sentinel is not None and h_dev is None:
                        h_dev = sentinel.init_state()
                    player_params = psync.before_dispatch(player_params)
                    with steady_guard(guard_on and train_windows > 0):
                        for u in update_chunks(
                            due, bytes_per_update=rb.sampled_bytes_per_update(batch_size)
                        ):
                            key, tk = jax.random.split(key)
                            if sentinel is not None:
                                params, opt_state, h_dev, counter_dev, last_losses = (
                                    train_phase_dev(
                                        params, opt_state, h_dev, rb.buffers, rb.cursor,
                                        tk, counter_dev, n_samples=u,
                                    )
                                )
                            else:
                                params, opt_state, counter_dev, last_losses = train_phase_dev(
                                    params, opt_state, rb.buffers, rb.cursor, tk,
                                    counter_dev, n_samples=u,
                                )
                            grad_step_counter += u
                    train_windows += 1
                    player_params = psync.after_dispatch(params, player_params)
            elif due > 0:
                with timer("Time/train_time"):
                    sample = rb.sample(
                        batch_size, n_samples=due
                    )  # (U, batch, *) block in one host call
                    batches = {
                        "obs": jnp.asarray(sample["obs"]),
                        "next_obs": jnp.asarray(sample["next_obs"]),
                        "actions": jnp.asarray(sample["actions"]),
                        "rewards": jnp.asarray(sample["rewards"][..., 0]),
                        "terminated": jnp.asarray(sample["terminated"][..., 0]),
                    }
                    batches = fabric.shard_batch(batches, axis=1)
                    # deferred sync AFTER the host-side sample/ship so that
                    # work overlaps the tail of the previous window's device
                    # compute (before_dispatch blocks on it — see PlayerSync)
                    player_params = psync.before_dispatch(player_params)
                    key, tk = jax.random.split(key)
                    if sentinel is not None:
                        if h_dev is None:
                            h_dev = sentinel.init_state()
                        h_dev, params, opt_state, last_losses = train_phase(
                            h_dev, params, opt_state, batches, tk,
                            jnp.int32(grad_step_counter),
                        )
                    else:
                        params, opt_state, last_losses = train_phase(
                            params, opt_state, batches, tk, jnp.int32(grad_step_counter)
                        )
                    grad_step_counter += due
                    player_params = psync.after_dispatch(params, player_params)

        # ---------------- training-health sentinel ---------------------------
        # the one D2H of the sentinel: a per-interval fetch of the tiny
        # HealthState, publishing Health/* through the hub and deciding
        # whether the divergence detector demands a rollback
        if (
            sentinel is not None
            and h_dev is not None
            and sentinel.should_poll(update, total_iters)
            and sentinel.poll(h_dev, policy_step) == "rollback"
        ):
            sentinel.begin_rollback(policy_step)  # raises past the budget
            rb_state, rb_dir = rollback_state(ckpt_mgr, fabric)
            if rb_state is None:
                raise DivergenceError(
                    f"training diverged at step {policy_step} with no committed "
                    "checkpoint to roll back to"
                )
            # restore exactly like a resume: params through the agent builder
            # (identical placement, so the guarded executable is reusable),
            # opt state/RNG streams replicated, grad-step counter rewound.
            # The replay buffer is NOT rolled back — transitions collected by
            # the diverged policy are still valid off-policy data.
            _, _, params = build_agent_fn(fabric, act_dim, cfg, obs_dim, rb_state["agent"])
            opt_state = fabric.replicate(rb_state["opt_state"])
            if rb_state.get("key") is not None:
                key = jnp.asarray(rb_state["key"])
            if rb_state.get("player_key") is not None:
                player_key = jax.device_put(jnp.asarray(rb_state["player_key"]), host)
            grad_step_counter = int(rb_state.get("grad_steps", grad_step_counter))
            counter_dev = None  # re-staged (replicated) before the next window
            h_dev = sentinel.reseed_state()  # diverged flag clears, dispatch count survives
            player_params = psync.init(params)
            last_losses = None
            fabric.print(
                f"health: diverged at step {policy_step} — rolled back to "
                f"committed snapshot {rb_dir}"
            )
            sentinel.rolled_back(policy_step, rb_dir)

        # ---------------- logging -------------------------------------------
        if cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or update == total_iters or cfg.dry_run
        ):
            if last_losses is not None:
                vl, pl, al = last_losses
                aggregator.update("Loss/value_loss", vl)
                aggregator.update("Loss/policy_loss", pl)
                aggregator.update("Loss/alpha_loss", al)
            last_log = flush_metrics(
                aggregator, timer, logger, policy_step, last_log,
                extra_metrics={
                    "Params/replay_ratio": grad_step_counter * fabric.world_size / max(policy_step, 1),
                    # deferred-sync staleness, made visible (ISSUE 12)
                    **psync.metrics(),
                },
            )

        # ---------------- checkpoint ----------------------------------------
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
                "pending_gradient_steps": window.pending,
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
        # the deferred-sync (decoupled) player may be stale: sync once more
        player_params = psync.init(params)
        test(actor, player_params, cfg, log_dir, logger)
    if logger is not None:
        logger.close()
