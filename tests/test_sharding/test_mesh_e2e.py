"""2-D (data, model) mesh end-to-end: the curated dreamer_v3 rule table must
change WHERE state lives without changing WHAT the train step computes.

One seeded DreamerV3-XS train step on a 2x4 data x model CPU mesh (8 fake
devices, conftest.py) vs the same step on a pure-data 8-device mesh:

* losses/params agree within the measured tensor-parallel drift tiers of
  tests/test_parallel/test_tensor_parallel.py (derivation in
  tests/test_parallel/test_tensor_parallel.py's tiers — GSPMD collective
  reassociation noise amplified through near-tie discrete latent samples);
* optimizer-state kernels are sharded exactly like their params (the
  state_io_shardings pin + the shared rule table);
* the program is compile-once: ONE train-phase executable, zero steady-state
  recompiles across repeat dispatches.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.config.compose import compose
from sheeprl_tpu.parallel import sharding as shd
from sheeprl_tpu.parallel.fabric import build_fabric

TINY = [
    "exp=dreamer_v3",
    "env=dummy",
    "env.id=discrete_dummy",
    "algo=dreamer_v3_XS",
    "algo.per_rank_batch_size=4",
    "algo.per_rank_sequence_length=8",
    "algo.horizon=4",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.mlp_keys.encoder=[]",
    # every sharded dim a multiple of 4 so the 2x4 mesh tiles without
    # demotions (the conv channels are the binding constraint)
    "algo.world_model.encoder.cnn_channels_multiplier=4",
    "algo.dense_units=32",
    "algo.world_model.recurrent_model.recurrent_state_size=32",
    "algo.world_model.transition_model.hidden_size=32",
    "algo.world_model.representation_model.hidden_size=32",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
    "fabric.accelerator=cpu",
    "fabric.devices=8",
    "fabric.precision=32-true",
]


def _one_step(mesh_shape=None, repeats=1):
    from gymnasium import spaces

    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_dv3_optimizers

    overrides = list(TINY)
    if mesh_shape:
        overrides.append(f"fabric.mesh_shape={mesh_shape}")
    cfg = compose(overrides)
    fabric = build_fabric(cfg)
    obs_space = spaces.Dict({"rgb": spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    world_model, actor, critic, params = build_agent(fabric, (4,), False, cfg, obs_space)
    wm_opt, actor_opt, critic_opt, opt_state = build_dv3_optimizers(fabric, cfg, params)
    train_phase = dv3.make_train_phase(
        fabric, cfg, world_model, actor, critic, wm_opt, actor_opt, critic_opt,
        cnn_keys=("rgb",), mlp_keys=(), is_continuous=False,
        params=params, opt_state=opt_state,
    )
    rng = np.random.default_rng(0)
    U, L, B = 1, 8, 8
    block = {
        "rgb": jnp.asarray(rng.integers(0, 255, (U, L, B, 64, 64, 3)).astype(np.uint8)),
        "actions": jnp.asarray(rng.integers(0, 2, (U, L, B, 4)).astype(np.float32)),
        "rewards": jnp.asarray(rng.normal(size=(U, L, B)).astype(np.float32)),
        "terminated": jnp.zeros((U, L, B), jnp.float32),
        "is_first": jnp.zeros((U, L, B), jnp.float32),
    }
    block = fabric.shard_batch(block, axis=2)
    params, opt_state, metrics = train_phase(
        params, opt_state, block, jax.random.PRNGKey(3), jnp.int32(0)
    )
    for i in range(1, repeats):
        params, opt_state, metrics = train_phase(
            params, opt_state, block, jax.random.PRNGKey(3), jnp.int32(i)
        )
    jax.block_until_ready(metrics)
    return fabric, train_phase, params, opt_state, jax.device_get(metrics)


def _paths_and_specs(tree):
    flat, _ = shd.tree_paths_and_leaves(tree)
    return {p: l.sharding.spec for p, l in flat if isinstance(l, jax.Array)}


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_dv3_2x4_mesh_loss_parity_and_opt_sharding():
    fab, train_phase, params, opt_state, m_tp = _one_step(
        "{data: 2, model: 4}", repeats=2
    )
    assert fab.model_axis == "model" and dict(fab.mesh.shape) == {"data": 2, "model": 4}

    # the curated table actually sharded the model: RSSM + actor/critic
    pspecs = _paths_and_specs(params)
    sharded = {p: s for p, s in pspecs.items() if any(e is not None for e in s)}
    assert any("recurrent_model/gru/fused/kernel" in p for p in sharded)
    assert any("actor" in p and "dense_0/kernel" in p for p in sharded)
    assert pspecs["actor/params/head/kernel"] == P("model", None)

    # opt-state kernels sharded EXACTLY like their params (state pinning):
    # every param kernel's spec appears on its mu/nu moments
    ospecs = _paths_and_specs(opt_state)
    matched = 0
    # target_critic is EMA-updated, not optimized: no moments to check
    optimized = {p: s for p, s in sharded.items() if not p.startswith("target_critic")}
    for opath, ospec in ospecs.items():
        for ppath, pspec in optimized.items():
            # param path world_model/params/X -> opt path world_model/../(mu|nu)/params/X
            group, suffix = ppath.split("/", 1)
            if opath.startswith(group) and opath.endswith(suffix) and (
                "/mu/" in opath or "/nu/" in opath
            ):
                assert ospec == pspec, (opath, ospec, pspec)
                matched += 1
    assert matched == 2 * len(optimized)  # one mu + one nu per sharded kernel

    # compile-once under TP: repeat dispatches hit ONE executable
    assert train_phase.cache_size() == 1

    # loss parity vs the pure-data mesh, within the measured TP drift tiers
    # (tests/test_parallel/test_tensor_parallel.py)
    _, _, p_dp, _, m_dp = _one_step(None, repeats=2)
    for a, b in zip(jax.tree_util.tree_leaves(m_tp), jax.tree_util.tree_leaves(m_dp)):
        b_arr = np.asarray(b)
        rtol = 1e-2 if np.all(np.abs(b_arr) > 10) else 1e-1
        np.testing.assert_allclose(np.asarray(a), b_arr, rtol=rtol, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(p_dp)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-2, atol=1e-3
        )


@pytest.mark.slow
def test_dv3_xlplus_500m_dryrun_2d_mesh():
    """ISSUE 7 acceptance: the 500M+ XL+ preset trains one step on an
    8-fake-device 2-D mesh with opt state sharded like params.  ~500M fp32
    params + Adam moments => >6 GiB of host RAM and a multi-minute XLA
    compile on small hosts — slow-marked, excluded from tier-1."""
    import os

    from gymnasium import spaces

    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_dv3_optimizers

    cfg = compose([
        "exp=dreamer_v3", "env=dummy", "env.id=discrete_dummy", "algo=dreamer_v3_XL+",
        "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[]", "algo.horizon=4",
        "fabric.accelerator=cpu", "fabric.devices=8",
        "fabric.mesh_shape={data: 2, model: 4}",
        # every sharded dim must tile the 500M preset cleanly: demotion = bug
        "sharding.undivisible=error",
    ])
    fabric = build_fabric(cfg)
    obs_space = spaces.Dict({"rgb": spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    world_model, actor, critic, params = build_agent(fabric, (4,), False, cfg, obs_space)
    n_wm = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params["world_model"]))
    assert n_wm >= 500_000_000, f"XL+ world model is {n_wm / 1e6:.0f}M params, expected 500M+"
    wm_opt, actor_opt, critic_opt, opt_state = build_dv3_optimizers(fabric, cfg, params)
    train_phase = dv3.make_train_phase(
        fabric, cfg, world_model, actor, critic, wm_opt, actor_opt, critic_opt,
        cnn_keys=("rgb",), mlp_keys=(), is_continuous=False,
        params=params, opt_state=opt_state,
    )
    rng = np.random.default_rng(0)
    U, L, B = 1, 2, 2
    block = {
        "rgb": jnp.asarray(rng.integers(0, 255, (U, L, B, 64, 64, 3)).astype(np.uint8)),
        "actions": jnp.asarray(rng.integers(0, 2, (U, L, B, 4)).astype(np.float32)),
        "rewards": jnp.asarray(rng.normal(size=(U, L, B)).astype(np.float32)),
        "terminated": jnp.zeros((U, L, B), jnp.float32),
        "is_first": jnp.zeros((U, L, B), jnp.float32),
    }
    block = fabric.shard_batch(block, axis=2)
    params, opt_state, metrics = train_phase(
        params, opt_state, block, jax.random.PRNGKey(0), jnp.int32(0)
    )
    jax.block_until_ready(metrics)
    assert np.isfinite(float(np.asarray(metrics[0])))
    # zero steady-state recompiles: the one executable serves a second step
    params, opt_state, metrics = train_phase(
        params, opt_state, block, jax.random.PRNGKey(0), jnp.int32(1)
    )
    jax.block_until_ready(metrics)
    assert train_phase.cache_size() == 1
