"""Pipeline parallelism (parallel/pipeline.py + the dreamer_v3 stage split).

The load-bearing claims, in dependency order:

1. the 1F1B schedule is a valid execution order (every unit once, deps
   respected, the per-stage in-flight memory bound holds);
2. gumbel-argmax sampling with hoisted noise is BIT-identical to
   ``jax.random.categorical`` — the sample-invariance law that lets the
   pipelined RSSM draw the exact posterior samples the monolithic baseline
   draws regardless of microbatching;
3. ``pipeline_value_and_grad`` equals monolithic ``jax.value_and_grad`` on
   a synthetic chain (pure reassociation, tight tolerance);
4. the ISSUE 16 acceptance cell: pipelined dreamer_v3 on a fake pipeline
   mesh matches the data-parallel baseline's losses/params within the
   tensor-parallel drift tiers (test_tensor_parallel.py), compile-once across steady windows under the
   armed transfer guard;
5. an indivisible microbatch split errors with the shard_batch-style
   message (the divisibility law), not an opaque XLA reshape error.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.config.compose import compose
from sheeprl_tpu.parallel import pipeline as pl
from sheeprl_tpu.parallel.fabric import build_fabric
from sheeprl_tpu.utils.distribution import OneHotCategorical

# same XS footprint as tests/test_sharding/test_mesh_e2e.py: every sharded
# dim a multiple of 4 so 4-way axis products tile without demotions
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


# --------------------------------------------------------------------------
# 1. schedule
# --------------------------------------------------------------------------

@pytest.mark.parametrize("stages", [1, 2, 3])
@pytest.mark.parametrize("microbatches", [3, 4, 6])
def test_one_f_one_b_is_a_valid_order(stages, microbatches):
    if microbatches < stages:
        pytest.skip("resolve_pipeline forbids M < S")
    order = pl.one_f_one_b(stages, microbatches)
    # every unit exactly once
    assert sorted(order) == sorted(
        [(op, s, m) for op in ("F", "B") for s in range(stages) for m in range(microbatches)]
    )
    pos = {unit: i for i, unit in enumerate(order)}
    live = [0] * stages
    peak = [0] * stages
    for op, s, m in order:
        if op == "F":
            if s > 0:
                assert pos[("F", s - 1, m)] < pos[("F", s, m)], "forward before its feeder"
            live[s] += 1
            peak[s] = max(peak[s], live[s])
        else:
            assert pos[("F", s, m)] < pos[("B", s, m)], "backward before its forward"
            if s < stages - 1:
                assert pos[("B", s + 1, m)] < pos[("B", s, m)], "backward before its cotangent"
            live[s] -= 1
    # the 1F1B liveness bound: at most S - s activations in flight at stage s
    for s in range(stages):
        assert peak[s] <= stages - s, (s, peak)
    if microbatches > stages > 1:
        # the defining 1F1B property (vs GPipe): the last stage starts
        # draining backwards before the first stage has injected everything
        assert pos[("B", stages - 1, 0)] < pos[("F", 0, microbatches - 1)]


def test_bubble_fraction():
    assert pl.bubble_fraction(1, 8) == 0.0
    assert pl.bubble_fraction(2, 4) == pytest.approx(1 / 5)
    assert pl.bubble_fraction(4, 12) == pytest.approx(3 / 15)


def test_resolve_pipeline_validates():
    assert not pl.resolve_pipeline({}).enabled
    with pytest.raises(ValueError, match="must be >= pipeline.stages"):
        pl.resolve_pipeline({"pipeline": {"stages": 4, "microbatches": 2}})
    with pytest.raises(ValueError, match="schedule"):
        pl.resolve_pipeline({"pipeline": {"stages": 2, "microbatches": 4, "schedule": "gpipe"}})
    spec = pl.resolve_pipeline({"pipeline": {"stages": 2, "microbatches": 4}})
    assert spec.enabled and spec.bubble_frac == pytest.approx(1 / 5)
    with pytest.raises(ValueError, match="implemented for"):
        spec.check_algo("dreamer_v1")
    spec.check_algo("dreamer_v3")  # no raise


# --------------------------------------------------------------------------
# 2. sample invariance
# --------------------------------------------------------------------------

def test_hoisted_noise_sampling_is_bit_identical():
    """The keystone: categorical(key, logits) == argmax(logits + gumbel) at
    logits shape/dtype, and row slices of the noise commute with argmax —
    so full-batch noise sliced per microbatch reproduces the baseline's
    samples EXACTLY."""
    key = jax.random.PRNGKey(7)
    logits = jax.random.normal(jax.random.PRNGKey(1), (16, 4, 8), jnp.float32)
    dist = OneHotCategorical(logits, unimix=0.01)
    baseline = dist.sample(key)
    noise = OneHotCategorical.sample_noise(key, dist.logits.shape, dist.logits.dtype)
    assert (dist.sample_from_noise(noise) == baseline).all()
    # microbatch slices: same rows, same bits
    for sl in (slice(0, 8), slice(8, 16)):
        mb = OneHotCategorical(logits[sl], unimix=0.01)
        assert (mb.sample_from_noise(noise[sl]) == baseline[sl]).all()
    # straight-through surface agrees too
    assert (dist.rsample_from_noise(noise) == dist.rsample(key)).all()


# --------------------------------------------------------------------------
# 3. microbatch plumbing + synthetic chain
# --------------------------------------------------------------------------

def test_split_merge_roundtrip_and_remainder_error():
    x = jnp.arange(2 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 3)
    parts = pl.split_microbatches(x, 4, axis=1)
    assert parts.shape == (4, 2, 2, 3)
    # contiguous row chunks on the batch axis
    np.testing.assert_array_equal(np.asarray(parts[1]), np.asarray(x[:, 2:4]))
    np.testing.assert_array_equal(np.asarray(pl.merge_microbatches(parts, axis=1)), np.asarray(x))
    with pytest.raises(ValueError, match="cannot split axis 1 .*3 microbatches"):
        pl.split_microbatches(x, 3, axis=1)


def test_chunked_rows_exact_and_remainder_error():
    x = jnp.arange(12 * 3, dtype=jnp.float32).reshape(12, 3)
    fn = lambda r: jnp.tanh(r @ jnp.ones((3, 5)))  # noqa: E731
    np.testing.assert_array_equal(np.asarray(pl.chunked_rows(fn, x, 4)), np.asarray(fn(x)))
    assert pl.chunked_rows(fn, x, 1) is not None  # passthrough path
    with pytest.raises(ValueError, match="imagination batch of 12 rows"):
        pl.chunked_rows(fn, x, 5)


def test_pipeline_value_and_grad_matches_monolithic():
    """3-stage synthetic chain vs plain value_and_grad on the full batch:
    identical math up to reassociation of the microbatch mean."""
    kp = jax.random.PRNGKey(0)
    params = {
        "w0": jax.random.normal(jax.random.fold_in(kp, 0), (6, 8)),
        "w1": jax.random.normal(jax.random.fold_in(kp, 1), (8, 8)),
        "w2": jax.random.normal(jax.random.fold_in(kp, 2), (8, 4)),
    }
    data = jax.random.normal(jax.random.fold_in(kp, 3), (16, 6))
    target = jax.random.normal(jax.random.fold_in(kp, 4), (16, 4))

    def s0(p, _c, const):
        return jnp.tanh(const["x"] @ p["w0"])

    def s1(p, c, const):
        del const
        return jnp.tanh(c @ p["w1"])

    def s2(p, c, const):
        err = c @ p["w2"] - const["y"]
        return jnp.mean(err**2), {"mae": jnp.mean(jnp.abs(err))}

    def monolithic(p, x, y):
        loss, aux = s2(p, s1(p, s0(p, None, {"x": x}), None), {"y": y})
        return loss, aux

    (ref_loss, ref_aux), ref_grads = jax.value_and_grad(monolithic, has_aux=True)(
        params, data, target
    )
    consts = pl.split_microbatches({"x": data, "y": target}, 4, axis=0)
    loss, aux, grads = pl.pipeline_value_and_grad(
        (s0, s1, s2), params, consts, microbatches=4
    )
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    np.testing.assert_allclose(float(aux["mae"].mean()), float(ref_aux["mae"]), rtol=1e-6)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(grads[k]), np.asarray(ref_grads[k]), rtol=1e-5, atol=1e-6
        )


def test_compose_pipeline_rules():
    from jax.sharding import PartitionSpec as P

    rules = (
        ("a", P(None, "model")),
        ("b", P("model", None)),
        ("c", None),
        ("d", lambda path, leaf, mesh: P(None, "model")),
    )
    both = dict(pl.compose_pipeline_rules(rules, has_model=True))
    assert both["a"] == P(None, ("pipeline", "model"))
    assert both["b"] == P(("pipeline", "model"), None)
    assert both["c"] is None
    assert both["d"]("p", None, None) == P(None, ("pipeline", "model"))
    pp_only = dict(pl.compose_pipeline_rules(rules, has_model=False))
    assert pp_only["a"] == P(None, "pipeline")


# --------------------------------------------------------------------------
# 4. the dreamer_v3 acceptance cell
# --------------------------------------------------------------------------

def _one_step(extra=(), repeats=1, windows=None):
    from gymnasium import spaces

    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_dv3_optimizers

    cfg = compose(list(TINY) + list(extra))
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
    if windows:
        # ISSUE 16 acceptance: N steady windows under the armed transfer
        # guard with ONE executable.  Keys/counter staged on device OUTSIDE
        # the guard; inside, only compiled dispatch + device-side arithmetic.
        from sheeprl_tpu.data.device_replay import steady_guard

        keys = [k for k in jax.random.split(jax.random.PRNGKey(9), windows)]
        counters = [jnp.int32(repeats + i) for i in range(windows)]
        jax.block_until_ready((params, opt_state))
        with steady_guard(True):
            for i in range(windows):
                params, opt_state, metrics = train_phase(
                    params, opt_state, block, keys[i], counters[i]
                )
    jax.block_until_ready(metrics)
    return fabric, train_phase, params, opt_state, jax.device_get(metrics)


PIPE_2STAGE = [
    "fabric.mesh_shape={data: 2, pipeline: 4}",
    "pipeline=2stage",  # stages: 2, microbatches: 4
    "pipeline.imagination_microbatches=2",
]


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_dv3_pipelined_matches_dp_baseline():
    """DP-vs-pipelined parity within the tensor-parallel drift tiers
    (same cell shape as test_mesh_e2e's DP-vs-TP): the 2-stage 1F1B pipeline
    on a {data: 2, pipeline: 4} mesh trains the same XS model to the same
    losses/params as the pure-data 8-device baseline."""
    fab, train_phase, p_pp, _, m_pp = _one_step(PIPE_2STAGE, repeats=2)
    assert fab.pipeline_axis == "pipeline" and fab.model_axis is None
    assert dict(fab.mesh.shape) == {"data": 2, "pipeline": 4}

    # weights actually tiled over the pipeline axis (composed rule table)
    from sheeprl_tpu.parallel import sharding as shd

    flat, _ = shd.tree_paths_and_leaves(p_pp)
    specs = {p: l.sharding.spec for p, l in flat if isinstance(l, jax.Array)}
    gru = [s for p, s in specs.items() if "recurrent_model/gru/fused/kernel" in p]
    assert gru and any("pipeline" in str(s) for s in gru), gru

    # compile-once under the pipeline: repeats hit ONE executable
    assert train_phase.cache_size() == 1

    _, _, p_dp, _, m_dp = _one_step((), repeats=2)
    for a, b in zip(jax.tree_util.tree_leaves(m_pp), jax.tree_util.tree_leaves(m_dp)):
        b_arr = np.asarray(b)
        rtol = 1e-2 if np.all(np.abs(b_arr) > 10) else 1e-1
        np.testing.assert_allclose(np.asarray(a), b_arr, rtol=rtol, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p_pp), jax.tree_util.tree_leaves(p_dp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2, atol=1e-3)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_dv3_pipelined_decoupled_rssm_matches_dp_baseline():
    """Same parity claim for the DecoupledRSSM branch (batched posterior
    sampling outside the scan — a different noise-consumption shape)."""
    dec = ["algo.world_model.decoupled_rssm=True"]
    _, _, p_pp, _, m_pp = _one_step(PIPE_2STAGE + dec)
    _, _, p_dp, _, m_dp = _one_step(dec)
    for a, b in zip(jax.tree_util.tree_leaves(m_pp), jax.tree_util.tree_leaves(m_dp)):
        b_arr = np.asarray(b)
        rtol = 1e-2 if np.all(np.abs(b_arr) > 10) else 1e-1
        np.testing.assert_allclose(np.asarray(a), b_arr, rtol=rtol, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p_pp), jax.tree_util.tree_leaves(p_dp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2, atol=1e-3)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_dv3_pipelined_compile_once_guarded_windows():
    """cache_size()==1 across steady update windows under the armed transfer
    guard — the compile-once law survives the trace-time-unrolled 1F1B
    schedule (ISSUE 16 acceptance).  Three windows are what would show a
    second executable: the first takes keys and counter staged on device
    where the warm-up call took fresh ones, the second is fed the first's
    donated outputs under the guard, the third those of the second (the
    signature's fixed point)."""
    _, train_phase, *_ = _one_step(PIPE_2STAGE, windows=3)
    assert train_phase.cache_size() == 1


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_dv3_microbatch_remainder_errors_clearly():
    """B=6 over microbatches=4: the divisibility law fires with the leaf
    spelled out (mirrors fabric.shard_batch), not an XLA reshape error."""
    from gymnasium import spaces

    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import build_dv3_optimizers

    cfg = compose(list(TINY) + PIPE_2STAGE)
    fabric = build_fabric(cfg)
    obs_space = spaces.Dict({"rgb": spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    world_model, actor, critic, params = build_agent(fabric, (4,), False, cfg, obs_space)
    wm_opt, actor_opt, critic_opt, opt_state = build_dv3_optimizers(fabric, cfg, params)
    train_phase = dv3.make_train_phase(
        fabric, cfg, world_model, actor, critic, wm_opt, actor_opt, critic_opt,
        cnn_keys=("rgb",), mlp_keys=(), is_continuous=False,
        params=params, opt_state=opt_state,
    )
    U, L, B = 1, 8, 6
    rng = np.random.default_rng(0)
    block = {
        "rgb": jnp.asarray(rng.integers(0, 255, (U, L, B, 64, 64, 3)).astype(np.uint8)),
        "actions": jnp.zeros((U, L, B, 4), jnp.float32),
        "rewards": jnp.zeros((U, L, B), jnp.float32),
        "terminated": jnp.zeros((U, L, B), jnp.float32),
        "is_first": jnp.zeros((U, L, B), jnp.float32),
    }
    block = fabric.shard_batch(block, axis=2)
    with pytest.raises(ValueError, match="multiples of pipeline.microbatches"):
        train_phase(params, opt_state, block, jax.random.PRNGKey(0), jnp.int32(0))


def test_pipeline_rejects_unsupported_algo():
    cfg = compose(list(TINY) + ["pipeline.stages=2", "pipeline.microbatches=4"])
    spec = pl.resolve_pipeline(cfg)
    with pytest.raises(ValueError, match="dreamer_v3"):
        spec.check_algo("p2e_dv3")


# --------------------------------------------------------------------------
# 5. the ≥5B XXL dryrun (abstract: params are eval_shape'd, not materialized)
# --------------------------------------------------------------------------

_XXL_DRYRUN = r"""
import jax, numpy as np, jax.numpy as jnp
from sheeprl_tpu.config.compose import compose
from sheeprl_tpu.parallel import sharding as shd
from sheeprl_tpu.parallel.fabric import build_fabric
from sheeprl_tpu.algos.dreamer_v3.agent import WorldModel

cfg = compose([
    "exp=dreamer_v3", "env=dummy", "env.id=discrete_dummy", "algo=dreamer_v3_XXL",
    "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[]",
    "fabric.accelerator=cpu", "fabric.devices=32",
    "fabric.mesh_shape={data: 2, pipeline: 4, model: 4}",
    "pipeline=2stage",
    "sharding.undivisible=error",  # every sharded dim must tile: demotion = bug
])
fabric = build_fabric(cfg)
assert fabric.pipeline_axis == "pipeline" and fabric.model_axis == "model"
wm_cfg = cfg.algo.world_model
wm = WorldModel(
    cnn_keys=("rgb",), mlp_keys=(), cnn_shapes={"rgb": (64, 64, 3)}, mlp_shapes={},
    actions_dim=(4,), cnn_mult=wm_cfg.encoder.cnn_channels_multiplier,
    dense_units=cfg.algo.dense_units, mlp_layers=cfg.algo.mlp_layers,
    recurrent_size=wm_cfg.recurrent_model.recurrent_state_size,
    hidden_size=wm_cfg.transition_model.hidden_size,
    repr_hidden_size=wm_cfg.representation_model.hidden_size,
    stochastic_size=wm_cfg.stochastic_size, discrete_size=wm_cfg.discrete_size,
    unimix=cfg.algo.unimix, bins=wm_cfg.reward_model.bins,
    learnable_initial_state=wm_cfg.learnable_initial_recurrent_state,
    decoupled_rssm=wm_cfg.decoupled_rssm, use_pallas_gru=False,
    fused_pallas_rssm=False, dtype=jnp.float32,
)
stoch = wm_cfg.stochastic_size * wm_cfg.discrete_size
rec = wm_cfg.recurrent_model.recurrent_state_size
shapes = jax.eval_shape(
    wm.init, jax.random.PRNGKey(0), {"rgb": jnp.zeros((1, 64, 64, 3), jnp.float32)},
    jnp.zeros((1, rec)), jnp.zeros((1, stoch)), jnp.zeros((1, 4)),
    jnp.ones((1, 1)), jax.random.PRNGKey(1),
)
n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
assert n >= 5_000_000_000, f"XXL world model is {n/1e9:.2f}B params, expected >=5B"
# undivisible=error: every matched spec tiles the 4x8 mesh cleanly, and the
# dominant kernels tile over the (pipeline, model) product
specs = shd.partition_specs(fabric.sharding_rules, shapes, fabric.mesh, undivisible="error")
flat, _ = shd.tree_paths_and_leaves(specs)
gru = [s for p, s in flat if "recurrent_model/gru/fused/kernel" in p]
assert gru and "pipeline" in str(gru[0]) and "model" in str(gru[0]), gru
print(f"XXL_OK {n}")
"""


@pytest.mark.slow
def test_dv3_xxl_5b_dryrun_4x8_mesh():
    """ISSUE 16 acceptance: the ≥5B XXL preset dryruns on a fake 4x8 mesh —
    param count and (pipeline, model) tiling verified ABSTRACTLY (6.1B fp32
    would need ~24 GiB just for params).  Subprocess: the 32-device XLA
    host-platform flag must be set before jax initializes."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
    out = subprocess.run(
        [sys.executable, "-c", _XXL_DRYRUN],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "XXL_OK" in out.stdout
