"""SAC-AE — pixel SAC with autoencoder
(reference: sheeprl/algos/sac_ae/sac_ae.py:119-502).

Gradient routing parity: critic loss trains critic AND encoder; actor
trains on stop-gradient features (at its own update frequency); the decoder
loss (MSE reconstruction + L2 latent penalty) trains encoder+decoder at its
own frequency; target critic/encoder EMA with separate taus.  The reference
needs ``DDPStrategy(find_unused_parameters=True)`` for this dance
(reference: cli.py:108-116) — the functional JAX formulation has no unused-
parameter problem: each loss differentiates exactly the param groups it
names, update cadences are ``lax.cond`` branches inside the scanned update.

Same TPU structure as SAC: host player, bulk-sampled update blocks, one
jitted dispatch per ratio window.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.sac.agent import ema_update, sample_action
from sheeprl_tpu.algos.sac.loss import actor_loss, alpha_loss, critic_loss
from sheeprl_tpu.algos.dreamer_v3.utils import normalize_obs_block
from sheeprl_tpu.algos.sac_ae.agent import build_agent
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.device_replay import (
    build_device_replay,
    fused_uniform_train,
    resolve_device_replay,
    sampled_bytes,
    steady_guard,
    update_chunks,
)
from sheeprl_tpu.parallel.fabric import PlayerSync
from sheeprl_tpu.utils.env import episode_stats, final_obs_rows, make_env, vectorize
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, flush_metrics
from sheeprl_tpu.utils.optim import build_optimizer
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import (
    Ratio,
    TrainWindow,
    merge_framestack,
    save_configs,
    window_scan,
)


def _prep(obs: Dict[str, np.ndarray], cnn_keys, mlp_keys) -> Dict[str, jax.Array]:
    out = {}
    for k in cnn_keys:
        x = np.asarray(obs[k])
        if x.ndim == 5:
            x = merge_framestack(x)
        out[k] = jnp.asarray(x, jnp.float32) / 255.0
    for k in mlp_keys:
        out[k] = jnp.asarray(np.asarray(obs[k], np.float32).reshape(np.asarray(obs[k]).shape[0], -1))
    return out


@register_algorithm()
def main(fabric: Any, cfg: Any) -> None:
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
        raise ValueError("SAC-AE supports continuous (Box) action spaces only, like the reference")
    obs_space = envs.single_observation_space
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys
    act_dim = int(np.prod(act_space.shape))
    act_low = np.asarray(act_space.low, np.float32)
    act_high = np.asarray(act_space.high, np.float32)

    state: Dict[str, Any] = {}
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)
    if state and state.get("key") is not None:
        # resume the train-dispatch RNG stream bit-exactly (rank-identical)
        key = jnp.asarray(state["key"])
    encoder, decoder, actor, critic, params = build_agent(
        fabric, act_dim, cfg, obs_space, state.get("agent")
    )

    actor_opt = build_optimizer(cfg.algo.actor.optimizer)
    critic_opt = build_optimizer(cfg.algo.critic.optimizer)
    alpha_opt = build_optimizer(cfg.algo.alpha.optimizer)
    encoder_opt = build_optimizer(cfg.algo.encoder.optimizer)
    decoder_opt = build_optimizer(cfg.algo.decoder.optimizer)
    opt_state = fabric.replicate(
        state.get("opt_state")
        or {
            "actor": actor_opt.init(params["actor"]),
            "critic": critic_opt.init(params["critic"]),
            "alpha": alpha_opt.init(params["log_alpha"]),
            "encoder": encoder_opt.init(params["encoder"]),
            "decoder": decoder_opt.init(params["decoder"]),
        }
    )

    aggregator = MetricAggregator(cfg.metric.aggregator.metrics if cfg.metric.log_level > 0 else {})
    timer.configure(cfg.metric)

    psync = PlayerSync(
        fabric, cfg, extract=lambda p: {"encoder": p["encoder"], "actor": p["actor"]}, params=params
    )
    host = psync.device  # single resolution of algo.player.device
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)
    encoder_tau = float(cfg.algo.encoder.tau)
    target_entropy = -float(act_dim)
    target_freq = int(cfg.algo.critic.per_rank_target_network_update_freq)
    actor_freq = int(cfg.algo.actor.per_rank_update_freq)
    decoder_freq = int(cfg.algo.decoder.per_rank_update_freq)
    l2_lambda = float(cfg.algo.decoder.l2_lambda)

    def to_env_actions(a: np.ndarray) -> np.ndarray:
        return act_low + (a + 1.0) * 0.5 * (act_high - act_low)

    def act_fn(p, obs, k, greedy=False):
        # key advances INSIDE the jitted step (one host dispatch per env step)
        k_sample, k_next = jax.random.split(k)
        feats = encoder.apply(p["encoder"], obs)
        a, _ = sample_action(actor, p["actor"], feats, k_sample, greedy=greedy)
        return a, k_next

    # compile-once routing: AOT-compiled per abstract signature, counted by
    # the recompile detector
    act_fn = fabric.compile(
        act_fn,
        name=f"{cfg.algo.name}.act_fn",
        static_argnames=("greedy",),
        max_recompiles=cfg.algo.get("max_recompiles"),
    )

    player_params = psync.init(params)

    # ---------------- one scanned update -------------------------------------
    def one_update(carry, batch_and_key):
        p, o_state, step_idx = carry
        batch, k = batch_and_key
        k_next, k_pi, k_dec = jax.random.split(k, 3)
        alpha = jnp.exp(p["log_alpha"])

        obs = normalize_obs_block(batch, cnn_keys, obs_keys, offset=0.0)
        next_obs = normalize_obs_block(
            {kk: batch[f"next_{kk}"] for kk in obs_keys}, cnn_keys, obs_keys, offset=0.0
        )

        # -- critic (trains critic AND encoder)
        next_feats = encoder.apply(p["target_encoder"], next_obs)
        next_a, next_lp = sample_action(actor, p["actor"], next_feats, k_next)
        target_qs = critic.apply(p["target_critic"], next_feats, next_a)
        target_v = jnp.min(target_qs, axis=0) - alpha * next_lp
        y = batch["rewards"] + gamma * (1.0 - batch["terminated"]) * target_v

        def c_loss(cp, ep):
            feats = encoder.apply(ep, obs)
            qs = critic.apply(cp, feats, batch["actions"])
            return critic_loss(qs, jax.lax.stop_gradient(y))

        vl, (c_grads, e_grads) = jax.value_and_grad(c_loss, argnums=(0, 1))(
            p["critic"], p["encoder"]
        )
        c_updates, new_c_opt = critic_opt.update(c_grads, o_state["critic"], p["critic"])
        e_updates, new_e_opt = encoder_opt.update(e_grads, o_state["encoder"], p["encoder"])
        p = {
            **p,
            "critic": optax.apply_updates(p["critic"], c_updates),
            "encoder": optax.apply_updates(p["encoder"], e_updates),
        }
        o_state = {**o_state, "critic": new_c_opt, "encoder": new_e_opt}

        # -- actor + temperature (every actor_freq updates, on sg features)
        def do_actor(operand):
            p, o_state = operand
            feats = jax.lax.stop_gradient(encoder.apply(p["encoder"], obs))

            def a_loss(ap):
                a, lp = sample_action(actor, ap, feats, k_pi)
                qs = critic.apply(p["critic"], feats, a)
                return actor_loss(alpha, lp, jnp.min(qs, axis=0)), lp

            (pl, lp), a_grads = jax.value_and_grad(a_loss, has_aux=True)(p["actor"])
            a_updates, new_a_opt = actor_opt.update(a_grads, o_state["actor"], p["actor"])
            al, t_grads = jax.value_and_grad(lambda la: alpha_loss(la, lp, target_entropy))(
                p["log_alpha"]
            )
            t_updates, new_t_opt = alpha_opt.update(t_grads, o_state["alpha"], p["log_alpha"])
            p = {
                **p,
                "actor": optax.apply_updates(p["actor"], a_updates),
                "log_alpha": p["log_alpha"] + t_updates,
            }
            return (p, {**o_state, "actor": new_a_opt, "alpha": new_t_opt}), (pl, al)

        def skip_actor(operand):
            return operand, (jnp.zeros(()), jnp.zeros(()))

        (p, o_state), (pl, al) = jax.lax.cond(
            step_idx % actor_freq == 0, do_actor, skip_actor, (p, o_state)
        )

        # -- autoencoder (every decoder_freq updates)
        def do_decoder(operand):
            p, o_state = operand

            def d_loss(ep, dp):
                feats = encoder.apply(ep, obs)
                recon = decoder.apply(dp, feats)
                # reference decoder objective (sheeprl/algos/sac_ae/sac_ae.py:100-109):
                # per decoder key, mse against the 5-bit-quantized + dithered
                # target (cnn; utils.py:68-76) PLUS 0.5*lambda*||h||^2 — the L2
                # penalty is counted once per key, matching the reference loop
                l2 = 0.5 * l2_lambda * jnp.mean(jnp.sum(feats**2, axis=-1))
                loss = 0.0
                for i, kk in enumerate(obs_keys):
                    if kk in cnn_keys:
                        # obs normalized to [0,1] upstream; round back to the
                        # exact uint8 grid before the 5-bit floor — the fp32
                        # /255 round-trip can land one bucket low at exact
                        # multiples of 8 (ADVICE r4)
                        raw = jnp.round(obs[kk] * 255.0)
                        quant = jnp.floor(raw / 8.0) / 32.0
                        dither = jax.random.uniform(jax.random.fold_in(k_dec, i), obs[kk].shape) / 32.0
                        target = quant + dither - 0.5
                    else:
                        target = obs[kk]
                    loss = loss + jnp.mean((recon[kk] - target) ** 2) + l2
                return loss

            dl, (e_grads, d_grads) = jax.value_and_grad(d_loss, argnums=(0, 1))(
                p["encoder"], p["decoder"]
            )
            e_updates, new_e_opt = encoder_opt.update(e_grads, o_state["encoder"], p["encoder"])
            d_updates, new_d_opt = decoder_opt.update(d_grads, o_state["decoder"], p["decoder"])
            p = {
                **p,
                "encoder": optax.apply_updates(p["encoder"], e_updates),
                "decoder": optax.apply_updates(p["decoder"], d_updates),
            }
            return (p, {**o_state, "encoder": new_e_opt, "decoder": new_d_opt}), dl

        def skip_decoder(operand):
            return operand, jnp.zeros(())

        (p, o_state), dl = jax.lax.cond(
            step_idx % decoder_freq == 0, do_decoder, skip_decoder, (p, o_state)
        )

        # -- EMA targets
        do_ema = (step_idx % target_freq) == 0
        new_tc = ema_update(p["target_critic"], p["critic"], tau)
        new_te = ema_update(p["target_encoder"], p["encoder"], encoder_tau)
        p = {
            **p,
            "target_critic": jax.tree.map(lambda n, o: jnp.where(do_ema, n, o), new_tc, p["target_critic"]),
            "target_encoder": jax.tree.map(lambda n, o: jnp.where(do_ema, n, o), new_te, p["target_encoder"]),
        }
        return (p, o_state, step_idx + 1), (vl, pl, al, dl)

    def train_phase(p, o_state, batches, k, step0):
        U = batches["rewards"].shape[0]
        keys = jax.random.split(k, U)
        (p, o_state, _), losses = window_scan(
            one_update, (p, o_state, step0), (batches, keys), unroll=bool(cnn_keys)
        )
        return p, o_state, jax.tree.map(lambda x: x.mean(), losses)

    train_phase = fabric.compile(
        train_phase,
        name=f"{cfg.algo.name}.train_phase",
        donate_argnums=(0, 1),
        max_recompiles=cfg.algo.get("max_recompiles"),
    )

    # ---------------- counters / buffer --------------------------------------
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

    # device-resident replay (data/device_replay.py): the whole ring — pixel
    # obs AND their stored next_<k> rows — lives in HBM sharded over the mesh
    # `data` axis, sampling compiled into the update dispatch (supersedes the
    # retired pixel-only DeviceMirror and the window_chunks byte probe)
    capacity = int(cfg.buffer.size) // num_envs
    memmap_dir = os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None
    use_device_replay = resolve_device_replay(cfg, fabric.accelerator)
    batch_size = int(cfg.algo.per_rank_batch_size) * fabric.local_world_size
    train_phase_dev = None
    if use_device_replay:
        def _prep_batch(b):
            out: Dict[str, jax.Array] = {
                "actions": b["actions"],
                "rewards": b["rewards"][..., 0],
                "terminated": b["terminated"][..., 0],
            }
            for k in cnn_keys:
                for src in (k, f"next_{k}"):
                    x = b[src]
                    if x.ndim >= 6:  # (U, B, S, H, W, C) framestack
                        x = merge_framestack(x, jnp)
                    out[src] = x  # uint8; /255 on device in the update body
            for k in mlp_keys:
                for src in (k, f"next_{k}"):
                    x = b[src].astype(jnp.float32)
                    out[src] = x.reshape(*x.shape[:2], -1)
            return out

        def _make_fused(ring):
            return fused_uniform_train(
                fabric,
                train_phase,
                ring,
                batch_size,
                _prep_batch,
                name=f"{cfg.algo.name}.train_phase_device",
                max_recompiles=cfg.algo.get("max_recompiles"),
            )

        # the ring's rows, exactly as the loop below stores them: every obs
        # key also keeps its next_<k> row
        leaf_specs = {
            "actions": ((act_dim,), np.float32),
            "rewards": ((1,), np.float32),
            "terminated": ((1,), np.float32),
        }
        for k in obs_keys:
            spec = (tuple(obs_space[k].shape) or (1,), obs_space[k].dtype)
            leaf_specs[k] = leaf_specs[f"next_{k}"] = spec
        # what Ratio will owe at the first train window (the burst)
        burst = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)(
            max(learning_starts, 1) * policy_steps_per_iter / fabric.world_size
        )
        rb, train_phase_dev = build_device_replay(
            fabric, cfg, capacity, num_envs, leaf_specs, _make_fused,
            train_state=(params, opt_state), first_window=burst,
            batch_bytes=sampled_bytes(leaf_specs, batch_size), memmap_dir=memmap_dir,
        )
    else:
        rb = ReplayBuffer(capacity, num_envs, memmap=cfg.buffer.memmap, memmap_dir=memmap_dir)
    if state and cfg.buffer.checkpoint and "rb" in state:
        rb.load_state_dict(state["rb"])

    guard_on = bool(cfg.buffer.get("transfer_guard", False)) and use_device_replay

    # rank-offset: each process's envs must be distinct streams or
    # multi-host DP collects the same data num_processes times
    obs, _ = envs.reset(seed=cfg.seed + rank * num_envs)
    last_losses = None
    counter_dev = None  # device-resident grad-step counter (zero-copy path)
    train_windows = 0  # completed dispatched windows (guards arm past warmup)
    # per-rank player key stream, advanced inside act_fn; the main `key`
    # stays rank-identical for train dispatches
    player_key = jax.device_put(
        # resume this rank's player RNG stream bit-exactly when saved
        jnp.asarray(state["player_key"]) if state and state.get("player_key") is not None
        else jax.random.fold_in(key, rank),
        host,
    )

    for update in range(start_iter, total_iters + 1):
        policy_step += num_envs * fabric.num_processes
        with timer("Time/env_interaction_time"):
            if update <= learning_starts and not state:
                env_actions = np.stack([act_space.sample() for _ in range(num_envs)])
                span = act_high - act_low
                actions = np.clip(2.0 * (env_actions - act_low) / np.where(span == 0, 1, span) - 1.0, -1, 1)
            else:
                with jax.default_device(host):
                    a, player_key = act_fn(player_params, _prep(obs, cnn_keys, mlp_keys), player_key)
                    actions = np.asarray(a)
                env_actions = to_env_actions(actions)
            next_obs, rewards, terminated, truncated, info = envs.step(env_actions)
            dones = np.logical_or(terminated, truncated)

            real_next = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
            done_idx = np.nonzero(dones)[0]
            if done_idx.size:
                final = final_obs_rows(info, done_idx, obs_keys)
                if final is not None:
                    for k in obs_keys:
                        real_next[k][done_idx] = final[k]

            step = {
                "actions": actions[None].astype(np.float32),
                "rewards": np.asarray(rewards, np.float32)[None, :, None],
                "terminated": terminated.astype(np.float32)[None, :, None],
            }
            for k in obs_keys:
                step[k] = np.asarray(obs[k])[None]
                step[f"next_{k}"] = real_next[k][None]
            rb.add(step)
            obs = next_obs
            for ep_ret, ep_len in episode_stats(info):
                aggregator.update("Rewards/rew_avg", ep_ret)
                aggregator.update("Game/ep_len_avg", ep_len)

        if update >= learning_starts:
            # windowed multi-iteration dispatch, same contract as sac.py
            # (algo.train_window_iters; update math/count unchanged)
            per_rank_gradient_steps = window.push(
                ratio(policy_step / fabric.world_size), update, learning_starts, total_iters
            )
            if per_rank_gradient_steps > 0 and train_phase_dev is not None:
                with timer("Time/train_time"):
                    # zero-copy steady state: sampling + gather compiled into
                    # the update dispatch, counter rides as device data, the
                    # transfer guard (optional) proves no implicit H2D past
                    # the first window; power-of-two chunks reuse executables
                    if counter_dev is None:
                        # replicated on the mesh, matching the program's output
                        # placement — a single-device stage would cost one
                        # extra (first-window) executable on multi-device
                        counter_dev = fabric.replicate(np.int32(grad_step_counter))
                    player_params = psync.before_dispatch(player_params)
                    with steady_guard(guard_on and train_windows > 0):
                        for u in update_chunks(
                            per_rank_gradient_steps,
                            bytes_per_update=rb.sampled_bytes_per_update(batch_size),
                        ):
                            key, tk = jax.random.split(key)
                            params, opt_state, counter_dev, last_losses = train_phase_dev(
                                params, opt_state, rb.buffers, rb.cursor, tk,
                                counter_dev, n_samples=u,
                            )
                            grad_step_counter += u
                    train_windows += 1
                    player_params = psync.after_dispatch(params, player_params)
            elif per_rank_gradient_steps > 0:
                with timer("Time/train_time"):
                    # host-numpy fallback: burst windows chunked into powers
                    # of two for compile reuse; one player sync per ratio
                    # window, not per chunk (a per-chunk refresh pulls full
                    # player params D2H each time — see the dreamer loop)
                    player_params = psync.before_dispatch(player_params)
                    for u in update_chunks(per_rank_gradient_steps):
                        sample = rb.sample(batch_size, n_samples=u)
                        batches: Dict[str, jax.Array] = {
                            "actions": jnp.asarray(sample["actions"]),
                            "rewards": jnp.asarray(sample["rewards"][..., 0]),
                            "terminated": jnp.asarray(sample["terminated"][..., 0]),
                        }
                        for k in cnn_keys:
                            for src in (k, f"next_{k}"):
                                x = np.asarray(sample[src])
                                # framestacked sample is (U, B, S, H, W, C) =
                                # 6-dim — merge stacks into channels before
                                # the encoder
                                if x.ndim >= 6:
                                    x = merge_framestack(x)
                                batches[src] = jnp.asarray(x)  # uint8; /255 on device
                        for k in mlp_keys:
                            for src in (k, f"next_{k}"):
                                x = np.asarray(sample[src], np.float32)
                                batches[src] = jnp.asarray(x.reshape(*x.shape[:2], -1))
                        batches = fabric.shard_batch(batches, axis=1)
                        key, tk = jax.random.split(key)
                        params, opt_state, last_losses = train_phase(
                            params, opt_state, batches, tk, jnp.int32(grad_step_counter)
                        )
                        grad_step_counter += u
                    player_params = psync.after_dispatch(params, player_params)

        if cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or update == total_iters or cfg.dry_run
        ):
            if last_losses is not None:
                vl, pl, al, dl = last_losses
                aggregator.update("Loss/value_loss", vl)
                aggregator.update("Loss/policy_loss", pl)
                aggregator.update("Loss/alpha_loss", al)
                aggregator.update("Loss/reconstruction_loss", dl)
            last_log = flush_metrics(
                aggregator, timer, logger, policy_step, last_log,
                extra_metrics=psync.metrics(),  # deferred-sync staleness (ISSUE 12)
            )

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

    envs.close()
    if getattr(rb, "spill", None) is not None:
        rb.spill.close()
    ckpt_mgr.finalize()
    if fabric.is_global_zero and cfg.algo.run_test and not ckpt_mgr.preempted:
        from sheeprl_tpu.algos.sac_ae.utils import test

        # the deferred-sync player may be one window stale: sync once more
        player_params = psync.init(params)
        test(encoder, actor, player_params, cfg, log_dir, logger)
    if logger is not None:
        logger.close()
