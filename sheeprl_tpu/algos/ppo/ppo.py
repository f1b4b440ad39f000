"""PPO, coupled topology — the canonical end-to-end slice (SURVEY.md §7.2).

Capability parity with the reference train script
(reference: sheeprl/algos/ppo/ppo.py:30-453): vectorized env rollout with
truncation bootstrapping, GAE at rollout end, epoch/minibatch clipped-PPO
updates, polynomial annealing of lr/clip/entropy, policy-step-paced logging,
checkpointing and final test episode.

TPU-native architecture (not a port) — shaped by accelerator latency:
* **Host player / device trainer in one process.**  Action selection during
  rollout runs a jitted policy on the HOST CPU device against a params copy
  refreshed once per iteration.  Per-env-step accelerator round-trips are
  never free; with a host player the rollout costs zero device syncs.  This is the single-process analogue of the
  reference's decoupled player/trainer topology
  (reference: sheeprl/algos/ppo/ppo_decoupled.py:32-365).
* **One dispatch per optimization phase.**  The full update — GAE, epoch
  loop, minibatch permutations, clipped losses, Adam — is a single jitted
  call (`lax.scan` over epochs × `lax.fori_loop` over minibatches on TPU;
  both levels unroll at trace time on XLA-CPU, where outlined loop bodies
  run ~5× slower — see `utils.window_scan`) with
  donated params: one host→device transfer of the rollout per iteration,
  one device→host transfer of the refreshed policy params.  The reference
  pays a DDP all-reduce + Python dispatch per minibatch instead.
* Parameters are replicated over the mesh and minibatches sharded over the
  ``data`` axis; XLA inserts the gradient all-reduce (DDP semantics without
  process groups).
"""

from __future__ import annotations

import os
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.algos.ppo.agent import build_agent, evaluate_actions, sample_actions
from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.utils import (
    actions_for_env,
    normalize_obs_keys,
    obs_to_np,
    prepare_obs,
    spaces_to_dims,
    test,
)
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.device_replay import stage_rollout, stage_scalar, steady_guard
from sheeprl_tpu.envs.jax.anakin import read_obs_fn
from sheeprl_tpu.envs.jax.registry import anakin_enabled
from sheeprl_tpu.telemetry.spans import SPANS
from sheeprl_tpu.utils.env import episode_stats, final_obs_rows, make_env, vectorize
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, flush_metrics
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.optim import build_optimizer, set_learning_rate
from sheeprl_tpu.utils.utils import gae, normalize_tensor, polynomial_decay, save_configs, should_unroll_updates, window_scan


def epoch_permutation(
    key, T: int, B: int, batch_size: int, num_minibatches: int, share_data: bool, n_shards: int
) -> jax.Array:
    """Flat sample order for one PPO epoch over the (T, B) rollout, laid out
    as ``num_minibatches`` consecutive ``batch_size`` slices.

    * ``share_data=True`` (or one shard): one permutation of the GLOBAL
      (T·B) pool, padded by wrap-around to fill the last minibatch — the
      reference's all-gather + DistributedSampler pool semantics
      (reference: sheeprl/algos/ppo/ppo.py:363-370,41-47).
    * ``share_data=False`` with ``n_shards`` processes: classic DDP — each
      process permutes only ITS OWN env columns (process r owns columns
      [r·B/n, (r+1)·B/n), the shard_batch concatenation order) and every
      minibatch interleaves an equal ``batch_size/n_shards`` slice from each
      process, so the sample gather stays shard-local on a TPU mesh.
    """
    if share_data or n_shards == 1:
        perm = jax.random.permutation(key, T * B)
        pad = num_minibatches * batch_size - (T * B)
        return jnp.concatenate([perm, perm[: max(pad, 0)]]) if pad > 0 else perm
    b_loc = B // n_shards
    rows = T * b_loc
    pr_bs = batch_size // n_shards

    def rank_perm(kr, r):
        pl = jax.random.permutation(kr, rows)
        t_idx, b_idx = pl // b_loc, pl % b_loc
        return t_idx * B + r * b_loc + b_idx

    perms = jax.vmap(rank_perm)(jax.random.split(key, n_shards), jnp.arange(n_shards))
    pad = num_minibatches * pr_bs - rows
    if pad > 0:
        perms = jnp.concatenate([perms, perms[:, :pad]], axis=1)
    return (
        perms.reshape(n_shards, num_minibatches, pr_bs)
        .transpose(1, 0, 2)
        .reshape(num_minibatches * batch_size)
    )


@register_algorithm()
def main(fabric: Any, cfg: Any) -> None:
    rank = fabric.global_rank
    key = fabric.seed_everything(cfg.seed)

    log_dir = get_log_dir(fabric, cfg.root_dir, cfg.run_name, base=cfg.get("log_dir", "logs/runs"))
    logger = get_logger(fabric, cfg, log_dir)
    ckpt_mgr = fabric.get_checkpoint_manager(cfg, log_dir)
    if fabric.is_global_zero:
        save_configs(cfg, log_dir)

    # ---------------- environments -----------------------------------------
    num_envs = cfg.env.num_envs
    use_anakin = anakin_enabled(cfg, fabric)
    # population mode (docs/population.md): vmap whole agents over a
    # population axis INSIDE the fused Anakin executable, with in-trace PBT
    pop_size = int(cfg.get("population", {}).get("size", 0) or 0)
    use_population = pop_size > 1
    if use_population and not use_anakin:
        raise ValueError(
            "population.size>1 rides the Anakin axis: it needs a pure-JAX env "
            "(env=jax_*), algo.anakin != False, and a single-process run"
        )
    if use_anakin:
        # Anakin mode (envs/jax/anakin.py): the env lives INSIDE the
        # compiled update — no vector-env processes exist at all
        from sheeprl_tpu.envs.jax.core import VectorJaxEnv
        from sheeprl_tpu.envs.jax.registry import jax_env_from_cfg

        envs = None
        with SPANS.setup_span("setup.env"):
            venv = VectorJaxEnv(jax_env_from_cfg(cfg), num_envs)
        obs_space = venv.single_observation_space
        act_space = venv.single_action_space
    else:
        envs = vectorize(
            cfg,
            [
                make_env(cfg, cfg.seed + rank * num_envs + i, rank, run_name=log_dir, vector_env_idx=i)
                for i in range(num_envs)
            ],
        )
        obs_space = envs.single_observation_space
        act_space = envs.single_action_space
    normalize_obs_keys(cfg, obs_space)
    actions_dim, is_continuous = spaces_to_dims(act_space)
    cnn_keys = tuple(cfg.algo.cnn_keys.encoder)
    mlp_keys = tuple(cfg.algo.mlp_keys.encoder)
    obs_keys = cnn_keys + mlp_keys
    # a fused rollout's uint8 pixel leaves back to float frames; a rollout
    # staged from the host passes through (envs/jax/anakin.py)
    read_obs = read_obs_fn(cnn_keys, obs_space)
    dist_type = cfg.get("distribution", {}).get("type", "auto")

    # ---------------- agent / optimizer -------------------------------------
    state: Dict[str, Any] = {}
    if cfg.checkpoint.resume_from:
        state = fabric.load(cfg.checkpoint.resume_from)
    if state and state.get("key") is not None:
        # resume the train-dispatch RNG stream bit-exactly (rank-identical)
        key = jnp.asarray(state["key"])
    with SPANS.setup_span("setup.agent"):
        agent, params = build_agent(
            fabric, actions_dim, is_continuous, cfg, obs_space,
            # population checkpoints hold STACKED (P, ...) params — restored in
            # the population block below, not through the single-agent loader
            None if (use_population and state) else state.get("agent"),
        )
    with SPANS.setup_span("setup.optimizer"):
        optimizer = build_optimizer(cfg.algo.optimizer, cfg.algo.max_grad_norm)
        if use_population:
            opt_state = None  # stacked per-member init happens in the population block
        else:
            opt_state = fabric.replicate(state.get("opt_state") or optimizer.init(params))

    aggregator = MetricAggregator(
        cfg.metric.aggregator.metrics if cfg.metric.log_level > 0 else {}
    )
    timer.configure(cfg.metric)

    # ---------------- host player (env-interaction policy) ------------------
    # on-policy loops honor algo.player.device (placement only; the sync
    # cadence options are meaningless on-policy: rollouts must use the
    # current weights)
    host = fabric.player_device(cfg)

    def policy_step_fn(p, obs, k, greedy=False):
        # key advances INSIDE the jitted step — one host dispatch per env
        # step instead of three (split/fold_in as separate tiny programs)
        k_sample, k_next = jax.random.split(k)
        out, value = agent.apply(p, obs)
        actions, logprob, _ = sample_actions(out, actions_dim, is_continuous, k_sample, greedy=greedy, dist_type=dist_type)
        return actions, logprob, value[..., 0], k_next

    # compile-once routing: AOT-compiled per abstract signature, counted by
    # the recompile detector (parallel/compile.py)
    policy_step_fn = fabric.compile(
        policy_step_fn,
        name=f"{cfg.algo.name}.policy_step",
        static_argnames=("greedy",),
        max_recompiles=cfg.algo.get("max_recompiles"),
    )

    @jax.jit
    def values_fn(p, obs):
        _, value = agent.apply(p, obs)
        return value[..., 0]

    player_params = fabric.to_host(params)

    # ---------------- single-dispatch train phase ---------------------------
    reduction = cfg.algo.loss_reduction
    clip_vloss = bool(cfg.algo.clip_vloss)
    normalize_adv = bool(cfg.algo.normalize_advantages)
    vf_coef = float(cfg.algo.vf_coef)
    gamma = float(cfg.algo.gamma)
    gae_lambda = float(cfg.algo.gae_lambda)
    update_epochs = int(cfg.algo.update_epochs)

    @jax.named_scope("update.loss")
    def loss_fn(p, batch, clip_coef, ent_coef):
        out, new_values = agent.apply(p, {k: batch[k] for k in obs_keys})
        new_logprobs, entropy = evaluate_actions(out, batch["actions"], actions_dim, is_continuous, dist_type=dist_type)
        adv = batch["advantages"]
        if normalize_adv:
            adv = normalize_tensor(adv)
        pg = policy_loss(new_logprobs, batch["logprobs"], adv, clip_coef, reduction)
        vl = value_loss(new_values[..., 0], batch["values"], batch["returns"], clip_coef, clip_vloss, reduction)
        ent = entropy_loss(entropy, reduction)
        return pg + vf_coef * vl + ent_coef * ent, (pg, vl, ent)

    def train_phase(
        p,
        o_state,
        rollout: Dict[str, jax.Array],
        last_obs: Dict[str, jax.Array],
        k,
        clip_coef,
        ent_coef,
        batch_size: int,
        num_minibatches: int,
        share_data: bool = True,
        n_shards: int = 1,
    ):
        """GAE + all epochs/minibatches in ONE device program.

        ``share_data`` selects the reference's two DP minibatch semantics
        (reference: sheeprl/algos/ppo/ppo.py:40-55,363-370):
        * True — every rank minibatches the GLOBAL rollout pool (the
          reference all-gathers + DistributedSampler); here a global
          permutation over the sharded (T·B) pool does it with no explicit
          gather — XLA moves only the rows each step needs.
        * False — classic DDP: each of the ``n_shards`` processes permutes
          only ITS OWN env columns and contributes ``batch_size/n_shards``
          rows per step; the sample gather stays shard-local (no cross-host
          traffic), gradients combine exactly as DDP's all-reduce would.
        """
        # --- GAE (values recomputed in one batched forward) ---
        T, B = rollout["rewards"].shape
        flat_obs = {key_: rollout[key_].reshape((T * B,) + rollout[key_].shape[2:]) for key_ in obs_keys}
        with jax.named_scope("gae"):  # the value pass over the whole rollout included
            _, values = agent.apply(p, read_obs(flat_obs))
            values = values[..., 0].reshape(T, B)
            next_value = values_fn(p, last_obs)
            returns, advantages = gae(
                rollout["rewards"], values, rollout["dones"], next_value, gamma, gae_lambda
            )

        flat = dict(flat_obs)
        flat["actions"] = rollout["actions"].reshape(T * B, -1)
        flat["logprobs"] = rollout["logprobs"].reshape(T * B)
        flat["values"] = values.reshape(T * B)
        flat["returns"] = returns.reshape(T * B)
        flat["advantages"] = advantages.reshape(T * B)

        # XLA-CPU runs conv-bearing bodies ~5x slower inside outlined loops
        # (scan/fori — see utils.window_scan); unroll BOTH update levels at
        # trace time when the total body count is small enough to compile
        unroll_updates = should_unroll_updates(cnn_keys, update_epochs * num_minibatches)

        def epoch_body(carry, key_e):
            p, o_state = carry
            perm = epoch_permutation(
                key_e, T, B, batch_size, num_minibatches, share_data, n_shards
            )

            def mb_body(i, carry2):
                p, o_state, losses = carry2
                with jax.named_scope("update.gather"):
                    idx = jax.lax.dynamic_slice(perm, (i * batch_size,), (batch_size,))
                    batch = read_obs({kk: jnp.take(vv, idx, axis=0) for kk, vv in flat.items()})
                (_, (pg, vl, ent)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    p, batch, clip_coef, ent_coef
                )
                with jax.named_scope("update.optim"):
                    updates, o_state = optimizer.update(grads, o_state, p)
                    p = optax.apply_updates(p, updates)
                return p, o_state, (pg, vl, ent)

            carry2 = (p, o_state, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())))
            if unroll_updates:
                for i in range(num_minibatches):
                    carry2 = mb_body(i, carry2)
                p, o_state, losses = carry2
            else:
                p, o_state, losses = jax.lax.fori_loop(
                    0, num_minibatches, mb_body, carry2
                )
            return (p, o_state), losses

        (p, o_state), losses = window_scan(
            epoch_body,
            (p, o_state),
            jax.random.split(k, update_epochs),
            unroll_limit=32,
            unroll=unroll_updates,
        )
        last_losses = jax.tree.map(lambda x: x[-1], losses)
        return p, o_state, last_losses

    # donate the STAGED rollout and bootstrap obs too (argnums 2/3): the one
    # dispatch consumes them exactly once, so XLA recycles their HBM for
    # activations instead of holding a dead copy across the update
    train_phase_fn = train_phase  # raw callable: the Anakin path fuses it
    train_phase = fabric.compile(
        train_phase,
        name=f"{cfg.algo.name}.train_phase",
        donate_argnums=(0, 1, 2, 3),
        static_argnames=("batch_size", "num_minibatches", "share_data", "n_shards"),
        max_recompiles=cfg.algo.get("max_recompiles"),
    )

    # ---------------- counters / schedules ----------------------------------
    # the train phase is a GLOBAL program: its batch covers all ranks
    sharded_envs, global_envs = fabric.env_sharding_plan(num_envs, "PPO")
    rollout_steps = int(cfg.algo.rollout_steps)
    T, B = rollout_steps, global_envs
    global_bs = min(int(cfg.algo.per_rank_batch_size) * fabric.world_size, T * B)
    num_minibatches = -(-T * B // global_bs)  # ceil: keep the tail
    # reference semantics (ppo.py:363-370): share_data only changes anything
    # across processes; the per-process shards must admit equal batch slices
    share_data = bool(cfg.buffer.get("share_data", False))
    n_shards = fabric.num_processes if sharded_envs else 1
    if n_shards > 1 and (global_bs % n_shards or B % n_shards):
        if not share_data:
            # share_data=False is the SHIPPED default (configs/exp/ppo.yaml),
            # so a hard error here would abort previously-working runs; the
            # fallback is instead documented in howto/configs.md (ADVICE r4)
            import warnings

            warnings.warn(
                f"buffer.share_data=False needs equal per-process batch slices "
                f"(batch {global_bs}, envs {B}, processes {n_shards}): falling "
                "back to the global-pool (share_data=True) sampler — pick a "
                "divisible algo.per_rank_batch_size/env.num_envs to keep "
                "shard-local sampling (see howto/configs.md)"
            )
        n_shards = 1  # uneven split: fall back to the global-pool sampler
    # GLOBAL env-step accounting: every process steps its own envs
    policy_steps_per_iter = num_envs * rollout_steps * fabric.num_processes
    if use_population:
        # every member steps its own env shard: the population multiplies
        # the env steps per fused update, so total_steps buys fewer updates
        policy_steps_per_iter *= pop_size
    total_iters = max(int(cfg.algo.total_steps) // policy_steps_per_iter, 1)
    if cfg.dry_run:
        total_iters = 1
    start_iter = int(state.get("update", 0)) + 1 if state else 1
    policy_step = int(state.get("policy_step", 0))
    last_log = int(state.get("last_log", 0))
    last_checkpoint = int(state.get("last_checkpoint", 0))

    initial_ent_coef = float(cfg.algo.ent_coef)
    initial_clip_coef = float(cfg.algo.clip_coef)
    base_lr = float(cfg.algo.optimizer.lr)
    clip_coef_v = initial_clip_coef
    ent_coef_v = initial_ent_coef
    # arm jax.transfer_guard("disallow") around steady-state train dispatches
    # (all staging above is explicit device_put, so the guard passing proves
    # the zero-implicit-H2D contract end to end)
    guard_on = bool(cfg.buffer.get("transfer_guard", False))

    # ---------------- Anakin fused rollout+train ----------------------------
    if use_anakin:
        from sheeprl_tpu.envs.jax.anakin import (
            init_actor_state,
            make_rollout_fn,
            traced_polynomial_decay,
        )

        def _sample(out, k):
            return sample_actions(out, actions_dim, is_continuous, k, dist_type=dist_type)

        rollout_fn = make_rollout_fn(
            venv,
            agent.apply,
            _sample,
            cnn_keys=cnn_keys,
            mlp_keys=mlp_keys,
            action_space=act_space,
            gamma=gamma,
            rollout_steps=rollout_steps,
        )

        def anakin_phase(p, o_state, actor, k):
            """``lax.scan`` env rollout + GAE + all epochs/minibatches in
            ONE device program.  Annealed coefficients are computed
            in-trace from the donated update counter, so the steady state
            performs zero host-to-device transfers of any kind."""
            k_roll, k_train, k_next = jax.random.split(k, 3)
            step0 = actor["update"]
            clip = (
                traced_polynomial_decay(step0, initial=initial_clip_coef, max_decay_steps=total_iters)
                if cfg.algo.anneal_clip_coef
                else jnp.float32(initial_clip_coef)
            )
            ent = (
                traced_polynomial_decay(step0, initial=initial_ent_coef, max_decay_steps=total_iters)
                if cfg.algo.anneal_ent_coef
                else jnp.float32(initial_ent_coef)
            )
            if cfg.algo.anneal_lr:
                o_state = set_learning_rate(
                    o_state,
                    traced_polynomial_decay(step0, initial=base_lr, max_decay_steps=total_iters, power=1.0),
                )
            actor, rollout, last_obs, stats = rollout_fn(p, actor, k_roll)
            p, o_state, losses = train_phase_fn(
                p,
                o_state,
                rollout,
                last_obs,
                k_train,
                clip,
                ent,
                batch_size=global_bs,
                num_minibatches=num_minibatches,
                share_data=share_data,
                n_shards=n_shards,
            )
            return p, o_state, actor, k_next, losses, stats

        if use_population:
            # ------------ population: vmap whole agents over P ------------
            from sheeprl_tpu import telemetry
            from sheeprl_tpu.population import (
                PBTConfig,
                PopulationMonitor,
                init_population_state,
                make_population_phase,
                tile_stack,
                write_population_summary,
            )

            pbt_cfg = PBTConfig.from_cfg(
                cfg,
                base={"lr": base_lr, "ent_coef": initial_ent_coef, "clip_coef": initial_clip_coef},
            )

            def member_phase(p, o_state, actor, k, hp):
                """ONE member's fused rollout+train with its hyperparameters
                as traced data (lr through the injected opt-state, clip/ent
                into the loss).  PBT replaces the anneal schedules, so the
                ``algo.anneal_*`` flags are inert in population mode."""
                k_roll, k_train = jax.random.split(k)
                o_state = set_learning_rate(o_state, hp["lr"])
                actor, rollout, last_obs, stats = rollout_fn(p, actor, k_roll)
                p, o_state, losses = train_phase_fn(
                    p,
                    o_state,
                    rollout,
                    last_obs,
                    k_train,
                    hp["clip_coef"],
                    hp["ent_coef"],
                    batch_size=global_bs,
                    num_minibatches=num_minibatches,
                    share_data=share_data,
                    n_shards=1,  # population runs are single-process (enforced above)
                )
                return p, o_state, actor, losses, stats

            population_step = fabric.compile(
                make_population_phase(member_phase, pbt_cfg),
                name=f"{cfg.algo.name}.population_phase",
                donate_argnums=(0, 1, 2, 3),
                max_recompiles=cfg.algo.get("max_recompiles"),
            )

            # stacked member state: all members start from the SAME init
            # (the hyperparameter spread diversifies them); opt-state is
            # per-member so exploit can copy weights+moments coherently;
            # each member gets its own seeded env shard
            pop_resume = state.get("population") if state else None
            if state:
                params = fabric.replicate(jax.tree.map(jnp.asarray, state["agent"]))
                opt_state = fabric.replicate(state["opt_state"])
            else:
                params = jax.device_put(tile_stack(params, pop_size), fabric.replicated)
                opt_state = jax.device_put(jax.vmap(optimizer.init)(params), fabric.replicated)

            def _init_member(k):
                env_state, _ = venv.reset(k)
                return {
                    "env": env_state,
                    "ep_ret": jnp.zeros((num_envs,), jnp.float32),
                    "ep_len": jnp.zeros((num_envs,), jnp.int32),
                }

            members = jax.vmap(_init_member)(
                jax.random.split(jax.random.fold_in(key, fabric.global_rank + 1), pop_size)
            )
            members["update"] = jnp.full((pop_size,), start_iter - 1, jnp.int32)
            pop_state = init_population_state(members, pbt_cfg, num_envs)
            if pop_resume:
                pop_state["fitness"] = jnp.asarray(pop_resume["fitness"])
                pop_state["ep_count"] = jnp.asarray(pop_resume["ep_count"])
                pop_state["exploits"] = jnp.asarray(pop_resume["exploits"])
                hp_state = {name: jnp.asarray(v) for name, v in pop_resume["hp"].items()}
            else:
                hp_state = pbt_cfg.init_hyperparams(jax.random.fold_in(key, pop_size))
            pop_state = jax.device_put(pop_state, fabric.replicated)
            hp_state = jax.device_put(hp_state, fabric.replicated)
            pop_monitor = PopulationMonitor()
            telemetry.HUB.register("population", pop_monitor)
            anakin_step = None
            actor_state = None
        else:
            anakin_step = fabric.compile(
                anakin_phase,
                name=f"{cfg.algo.name}.anakin_phase",
                donate_argnums=(0, 1, 2),
                max_recompiles=cfg.algo.get("max_recompiles"),
            )
            with SPANS.setup_span("setup.env"):  # the envs' first reset
                actor_state = init_actor_state(
                    fabric, venv, jax.random.fold_in(key, fabric.global_rank + 1), start_iter - 1, sharded_envs
                )
        rb = None
    else:
        rb = ReplayBuffer(
            rollout_steps,
            num_envs,
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{rank}") if cfg.buffer.memmap else None,
            obs_keys=obs_keys,
        )

    # ---------------- main loop ---------------------------------------------
    step_data: Dict[str, np.ndarray] = {}
    # rank-offset: each process's envs must be distinct streams or
    # multi-host DP collects the same data num_processes times
    if envs is not None:
        with SPANS.setup_span("setup.env"):
            obs, _ = envs.reset(seed=cfg.seed + rank * num_envs)
    last_losses = None
    # per-rank player key stream, advanced inside policy_step_fn; the main
    # `key` stays rank-identical for train dispatches
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
        if use_anakin:
            # -------- fused rollout+train: ONE dispatch per update ---------
            with timer("Time/train_time"):
                with steady_guard(guard_on and update > start_iter):
                    if use_population:
                        # the WHOLE population trains in this one dispatch
                        params, opt_state, pop_state, hp_state, key, last_losses, ep_stats = (
                            population_step(params, opt_state, pop_state, hp_state, key)
                        )
                    else:
                        params, opt_state, actor_state, key, last_losses, ep_stats = anakin_step(
                            params, opt_state, actor_state, key
                        )
                if use_population:
                    # per-member (P,) losses → scalars for the aggregator
                    last_losses = jax.tree.map(lambda x: x.mean(), last_losses)
                policy_step += policy_steps_per_iter
            if cfg.metric.log_level > 0:
                # completion arrays are tiny; the pull is D2H (legal under
                # the H2D-scoped steady guard)
                from sheeprl_tpu.envs.jax.anakin import episode_stats_from_device

                # the loop's first wait for the fused dispatch: its host time
                # is the device's, so it gets a span of its own
                with SPANS.span("stats.pull", phase=False):
                    rets, lens = episode_stats_from_device(ep_stats)
                for ep_ret, ep_len in zip(rets, lens):
                    aggregator.update("Rewards/rew_avg", float(ep_ret))
                    aggregator.update("Game/ep_len_avg", int(ep_len))
                if use_population:
                    # Population/* hub family: tiny D2H pulls on the logging
                    # cadence (the guard is H2D-scoped)
                    pop_monitor.observe(
                        pop_state["fitness"], hp_state, pop_state["exploits"]
                    )
        else:
            with timer("Time/env_interaction_time"):
                with jax.default_device(host):
                    for _ in range(rollout_steps):
                        policy_step += num_envs * fabric.num_processes

                        dev_obs = prepare_obs(obs, cnn_keys, mlp_keys)
                        actions, logprobs, _, player_key = policy_step_fn(
                            player_params, dev_obs, player_key
                        )
                        actions_np = np.asarray(actions)
                        next_obs, rewards, terminated, truncated, info = envs.step(
                            actions_for_env(actions_np, act_space)
                        )
                        dones = np.logical_or(terminated, truncated)
                        rewards = np.asarray(rewards, np.float32)

                        # truncation bootstrap: r += γ·V(real final obs)
                        # (reference: ppo.py:287-306).  The final-obs batch is
                        # padded to the full env count so values_fn keeps ONE
                        # static shape (no per-count recompiles).
                        if np.any(truncated):
                            final_obs = final_obs_rows(info, np.nonzero(truncated)[0], obs_keys)
                            if final_obs is not None:
                                padded = {k: np.asarray(next_obs[k]).copy() for k in obs_keys}
                                for k in obs_keys:
                                    padded[k][truncated] = final_obs[k]
                                vals = np.asarray(
                                    values_fn(player_params, prepare_obs(padded, cnn_keys, mlp_keys))
                                )
                                rewards[truncated] += gamma * vals[truncated]

                        for k in obs_keys:
                            step_data[k] = np.asarray(obs[k])[None]
                        step_data["actions"] = actions_np[None]
                        step_data["logprobs"] = np.asarray(logprobs)[None]
                        # values are NOT stored: train_phase recomputes them with
                        # the same (unchanged) params in one batched forward
                        step_data["rewards"] = rewards[None]
                        step_data["dones"] = dones[None].astype(np.float32)
                        rb.add({k: v[..., None] if v.ndim == 2 else v for k, v in step_data.items()})

                        obs = next_obs
                        for ep_ret, ep_len in episode_stats(info):
                            aggregator.update("Rewards/rew_avg", ep_ret)
                            aggregator.update("Game/ep_len_avg", ep_len)

            # ---------------- one-dispatch optimization -------------------------
            with timer("Time/train_time"):
                # donated device staging: the rollout is normalized on HOST
                # numpy, staged with EXPLICIT device_puts (transfer-guard-clean,
                # data/device_replay.stage_rollout) and donated into the train
                # phase, which consumes it exactly once per dispatch — its HBM is
                # recycled for activations.  buffer.transfer_guard=true arms
                # jax.transfer_guard("disallow") around the dispatch to prove no
                # implicit H2D rides along.
                local = rb.buffer
                host_rollout = {k: obs_to_np(local[k], k in cnn_keys, rollout=True) for k in obs_keys}
                host_rollout["actions"] = np.asarray(local["actions"])
                host_rollout["logprobs"] = np.asarray(local["logprobs"][..., 0])
                host_rollout["rewards"] = np.asarray(local["rewards"][..., 0])
                host_rollout["dones"] = np.asarray(local["dones"][..., 0])
                # multi-host: each process contributes its local env rows and the
                # global batch is their concatenation (axis=1); single-process
                # replicates (env-axis minibatch gathers are cheapest replicated)
                rollout = stage_rollout(fabric, host_rollout, axis=1, sharded=sharded_envs)
                host_last = {k: obs_to_np(np.asarray(obs[k]), k in cnn_keys) for k in obs_keys}
                last_obs_dev = stage_rollout(fabric, host_last, axis=0, sharded=sharded_envs)
                key, tk = jax.random.split(key)
                clip_dev = stage_scalar(clip_coef_v)
                ent_dev = stage_scalar(ent_coef_v)
                with steady_guard(guard_on and update > start_iter):
                    params, opt_state, last_losses = train_phase(
                        params,
                        opt_state,
                        rollout,
                        last_obs_dev,
                        tk,
                        clip_dev,
                        ent_dev,
                        batch_size=global_bs,
                        num_minibatches=num_minibatches,
                        share_data=share_data,
                        n_shards=n_shards,
                    )
                # refresh the host player once per iteration (one d2h transfer)
                player_params = fabric.to_host(params)

        # ---------------- schedules -----------------------------------------
        # (Anakin mode anneals in-trace from the donated update counter —
        # host-side annealing would be a per-update H2D write)
        if not use_anakin:
            if cfg.algo.anneal_lr:
                new_lr = polynomial_decay(update, initial=base_lr, final=0.0, max_decay_steps=total_iters, power=1.0)
                opt_state = set_learning_rate(opt_state, new_lr)
            if cfg.algo.anneal_clip_coef:
                clip_coef_v = polynomial_decay(update, initial=initial_clip_coef, final=0.0, max_decay_steps=total_iters)
            if cfg.algo.anneal_ent_coef:
                ent_coef_v = polynomial_decay(update, initial=initial_ent_coef, final=0.0, max_decay_steps=total_iters)

        # ---------------- logging --------------------------------------------
        if cfg.metric.log_level > 0 and (
            policy_step - last_log >= cfg.metric.log_every or update == total_iters or cfg.dry_run
        ):
            if last_losses is not None:
                pg, vl, ent = last_losses
                aggregator.update("Loss/policy_loss", pg)
                aggregator.update("Loss/value_loss", vl)
                aggregator.update("Loss/entropy_loss", ent)
            last_log = flush_metrics(aggregator, timer, logger, policy_step, last_log)

        # ---------------- checkpoint -----------------------------------------
        # cadence + final save_last + preemption, via the fault-tolerant
        # subsystem (async snapshot → durable commit; docs/checkpointing.md)
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
                "batch_size": global_bs,
            }
            if use_population:
                # params/opt_state above are already the stacked (P, ...)
                # pytrees; the PBT carry rides its own subtree
                ckpt_state["population"] = {
                    "fitness": pop_state["fitness"],
                    "ep_count": pop_state["ep_count"],
                    "exploits": pop_state["exploits"],
                    "hp": hp_state,
                }
            fabric.call(
                "on_checkpoint_coupled",
                ckpt_path=os.path.join(log_dir, "checkpoint", f"ckpt_{policy_step}_{rank}.ckpt"),
                state=ckpt_state,
            )
        if ckpt_mgr.preempted:
            fabric.print(f"Preemption: committed checkpoint at step {policy_step}, exiting")
            break

    SPANS.end_iteration()
    profiler.close()
    if envs is not None:
        envs.close()
    ckpt_mgr.finalize()
    if use_population and fabric.is_global_zero:
        # machine-readable member snapshot for the run_ci PBT drill
        write_population_summary(log_dir, pop_state, hp_state, policy_step)
    if fabric.is_global_zero and cfg.algo.run_test and not ckpt_mgr.preempted:
        if use_population:
            # eval the current BEST member (fitness argmax)
            best = int(np.asarray(pop_state["fitness"]).argmax())
            player_params = fabric.to_host(jax.tree.map(lambda x: x[best], params))
        elif use_anakin:
            # the fused path never refreshes the host player copy — pull
            # the final params once for the eval episode
            player_params = fabric.to_host(params)
        test(agent, player_params, cfg, log_dir, logger)
    if logger is not None:
        logger.close()


def _obs_to_device(arr: np.ndarray, is_image: bool) -> jax.Array:
    from sheeprl_tpu.algos.ppo.utils import obs_to_np

    return jnp.asarray(obs_to_np(arr, is_image, rollout=True))
