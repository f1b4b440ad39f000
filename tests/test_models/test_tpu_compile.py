"""Compile the TPU-only code for a DESCRIBED v5e chip (no chip attached).

The TPU compiler is installed with libtpu and compiles for a topology that is
described rather than attached, so these cases guard — at no chip time — what
interpret mode and the CPU backend cannot see: Mosaic lowering of the Pallas
kernels at real DreamerV3 widths (tiling, VMEM budget), and the TPU lowering
of the DV3-S world-model forward+backward.  Nothing runs; a compile that
passes is not a chip run.

All cases live in THIS file and the topology is described inside a
module-scoped, non-autouse fixture: only the xdist worker that is handed this
file loads libtpu (one process at a time may hold it).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    """A ``SingleDeviceSharding`` on chip 0 of a described ``v5e:2x2`` host,
    with the persistent compilation cache off around the module's compiles (a
    described-chip entry can be written but never read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises here means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("batch", [16, 1024])
def test_fused_gru_compiles_for_v5e_at_dv3_s_widths(one_chip, batch):
    """LayerNorm-GRU cell, DV3-S: dense 512 -> recurrent 512."""
    from sheeprl_tpu.ops.gru_pallas import fused_layernorm_gru

    D = H = 512
    s = lambda *shape: _spec(one_chip, *shape)  # noqa: E731
    text = _compiled_text(
        lambda x, h, w, sc, b: fused_layernorm_gru(x, h, w, sc, b, interpret=False),
        s(batch, D), s(batch, H), s(D + H, 3 * H), s(3 * H), s(3 * H),
    )
    assert "tpu_custom_call" in text


def _rssm_specs(one_chip, batch, ZA, D, H):
    s = lambda *shape: _spec(one_chip, *shape)  # noqa: E731
    return (
        s(batch, ZA), s(batch, H), s(ZA, D), s(D), s(D), s(D),
        s(D + H, 3 * H), s(3 * H), s(3 * H),
    )


@pytest.mark.parametrize("batch", [16, 1024])
def test_fused_rssm_compiles_for_v5e_at_dv3_s_widths(one_chip, batch):
    """Whole recurrent path resident in VMEM, DV3-S: z(32x32)+a(5) = 1029 in."""
    from sheeprl_tpu.ops.rssm_pallas import fused_rssm_recurrent

    text = _compiled_text(
        lambda *a: fused_rssm_recurrent(*a, interpret=False),
        *_rssm_specs(one_chip, batch, ZA=1029, D=512, H=512),
    )
    assert "tpu_custom_call" in text


def test_tiled_rssm_compiles_for_v5e_at_dv3_xl_widths(one_chip):
    """XL (dense 1024, recurrent 4096) exceeds the resident kernel's VMEM
    budget: the planner must pick the column-tiled kernel and Mosaic accept it."""
    from sheeprl_tpu.ops.rssm_pallas import fused_rssm_recurrent

    text = _compiled_text(
        lambda *a: fused_rssm_recurrent(*a, interpret=False),
        *_rssm_specs(one_chip, 16, ZA=1029, D=1024, H=4096),
    )
    assert "tpu_custom_call" in text


def test_dv3_s_world_model_fwd_bwd_compiles_for_v5e(one_chip):
    """The DV3-S world model's loss and gradients at B=16, L=64 on 64x64x3
    uint8 pixels, bf16-mixed — the shapes ``chip_smoke.py`` trains at."""
    from gymnasium import spaces

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_wm_stages
    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.parallel.fabric import Fabric

    cfg = compose(["exp=dreamer_v3", "algo=dreamer_v3_S", "env=jax_forage"])
    fabric = Fabric(devices=1, accelerator="cpu", precision="bf16-mixed")
    obs_space = spaces.Dict({"rgb": spaces.Box(0, 255, (64, 64, 3), np.uint8)})
    world_model, _, _, params = build_agent(fabric, (5,), False, cfg, obs_space)
    wm_forward, _, _ = make_wm_stages(cfg, world_model, ("rgb",), ())

    def loss_and_grads(wm_params, data, key):
        return jax.value_and_grad(lambda p: wm_forward(p, data, key)[0])(wm_params)

    L, B = 64, 16
    on_chip = lambda x: _spec(one_chip, *x.shape, dtype=x.dtype)  # noqa: E731
    data = {
        "rgb": _spec(one_chip, L, B, 64, 64, 3, dtype=jnp.uint8),
        "actions": _spec(one_chip, L, B, 5),
        "rewards": _spec(one_chip, L, B),
        "terminated": _spec(one_chip, L, B),
        "is_first": _spec(one_chip, L, B),
    }
    key = on_chip(jax.random.PRNGKey(0))
    compiled = (
        jax.jit(loss_and_grads)
        .lower(jax.tree.map(on_chip, params["world_model"]), data, key)
        .compile()
    )
    memory = compiled.memory_analysis()
    # fits the 16 GB chip with room for the ring (it needs about 1 GB)
    assert 0 < memory.temp_size_in_bytes < 4 * 2**30
    assert "convolution" in compiled.as_text()


# --------------------------------------------------------------------------
# the device replay ring: pixel leaves are stored lane-dense, so the chip
# indexes them in place (data/device_replay.stored_feature)
# --------------------------------------------------------------------------

def _described_ring(one_chip, window, n_envs):
    """A ``DeviceReplay`` whose programs lower for the described chip.  Built
    without a mesh (nothing can be put on a described device), then handed
    the one-chip mesh its ``_ops``/``access_extra_bytes`` read."""
    from jax.sharding import Mesh

    from sheeprl_tpu.data.device_replay import DeviceReplay
    from sheeprl_tpu.parallel.sharding import replay_sharding

    ring = DeviceReplay(window, n_envs)
    ring._mesh = Mesh(np.array([one_chip._device]), ("data",))
    ring._sharding = replay_sharding(ring._mesh, n_envs, "data")
    return ring


@pytest.mark.parametrize("n_envs", [1, 4])
@pytest.mark.parametrize("feat", [(64, 64, 3), (84, 84, 4)], ids=["64x64x3", "84x84x4_padded"])
def test_ring_write_and_gather_index_a_pixel_leaf_in_place_on_v5e(one_chip, feat, n_envs):
    """The ring's real donated scatter and its gather at W=8192: temporaries
    under 1% of the ring (they were 2.0x each while the leaf was stored
    ``(W, E, H, W, C)``), and the stored leaf dense on the device."""
    from sheeprl_tpu.data.device_replay import ring_device_bytes

    window = 8192
    specs = {"rgb": (feat, np.uint8)}
    ring = _described_ring(one_chip, window, n_envs)
    raw = window * n_envs * int(np.prod(feat))
    assert ring_device_bytes(specs, window, n_envs, ring._sharding) <= 1.01 * raw
    read, write = ring.access_extra_bytes(specs)
    assert read < 0.01 * raw and write < 0.01 * raw, (read / raw, write / raw)


def test_fused_dv3_gather_holds_no_copy_of_the_ring_on_v5e(one_chip):
    """``sample_sequences`` as the fused DV3-S train program runs it (U=4,
    L=64, B=16 over the five DV3 leaves, pixels normalised as the encoder's
    first op does): no ``copy`` of the ring's shape in the compiled text, no
    temporary of its size."""
    import re

    window, n_envs = 65536, 4
    specs = {
        "rgb": ((64, 64, 3), np.uint8), "actions": ((5,), np.float32),
        "rewards": ((1,), np.float32), "terminated": ((1,), np.float32), "is_first": ((1,), np.float32),
    }
    ring = _described_ring(one_chip, window, n_envs)

    def gather(buffers, cursor, key):
        blocks = ring.sample_sequences(buffers, cursor, key, 16, 64, 4)
        assert blocks["rgb"].shape == (4, 64, 16, 64, 64, 3)
        return dict(blocks, rgb=blocks["rgb"].astype(jnp.bfloat16) / 255.0 - 0.5)

    cursor = {k: _spec(one_chip, n_envs, dtype=jnp.int32) for k in ("pos", "filled")}
    key = _spec(one_chip, 2, dtype=jnp.uint32)
    compiled = jax.jit(gather).lower(ring.abstract_buffers(specs), cursor, key).compile()
    ring_shaped = re.findall(rf"= u8\[{window},{n_envs},\d+\]\S* copy\(", compiled.as_text())
    assert not ring_shaped, ring_shaped
    raw = window * n_envs * 64 * 64 * 3
    # the gathered block (0.2 GiB as the chip pads it), nothing of the ring's size (3 GiB)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1 * raw


def test_player_refresh_beside_the_train_state_is_a_real_copy_on_v5e(one_chip):
    """``Fabric.copy_to``'s one-executable tree copy as the chip's compiler builds it: every
    output a buffer of its own (the train phase donates the source), no temporaries."""
    from sheeprl_tpu.parallel.fabric import _copy_tree, tree_bytes

    tree = {"wm": {"kernel": _spec(one_chip, 1536, 1536), "bias": _spec(one_chip, 1536)},
            "actor": {"kernel": _spec(one_chip, 1536, 5, dtype=jnp.bfloat16)}}
    compiled = _copy_tree.lower(tree).compile()
    assert "input_output_alias" not in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 0 and memory.temp_size_in_bytes == 0
    assert memory.output_size_in_bytes >= tree_bytes(tree)


# --------------------------------------------------------------------------
# the fused rollout's pixel store (envs/jax/anakin.make_rollout_fn): frames
# stay the env's uint8, lane-dense, and are normalised where they are read
# --------------------------------------------------------------------------

def _unfused_instructions(text):
    """``(op, dtype, elements)`` of every array an instruction outside a fusion's own computation
    produces: what stands in memory between two kernels of the compiled program."""
    import math
    import re

    head = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
    inst = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (.*?) ([a-z\-]+)\(")
    array = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]")
    fused = True
    for line in text.splitlines():
        m = head.match(line)
        if m:
            fused = m.group(1).startswith("fused_computation")
        elif not fused and (m := inst.match(line)):
            for dtype, dims in array.findall(m.group(1)):
                yield m.group(2), dtype, math.prod(int(d) for d in dims.split(",") if d)


def pixel_rollout_and_update():
    """``(program, specs)``: ``make_rollout_fn`` on 512 multiroom envs x 128 steps, then what
    ``ppo.train_phase`` does with the frames: the value pass over the pool and one minibatch of 16,384
    gathered and differentiated, in bf16.  ``specs(sharding)`` gives the arguments' shapes."""
    from sheeprl_tpu.algos.ppo.agent import build_agent, sample_actions
    from sheeprl_tpu.algos.ppo.utils import spaces_to_dims
    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.envs.jax import anakin
    from sheeprl_tpu.envs.jax.core import VectorJaxEnv
    from sheeprl_tpu.envs.jax.multiroom import JaxMultiRoom
    from sheeprl_tpu.parallel.fabric import Fabric

    T, B, minibatch = 128, 512, 16384
    cfg = compose([
        "exp=ppo", "env=jax_multiroom", "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[]",
        "algo.dense_units=512", "algo.mlp_layers=1", "algo.encoder.cnn_features_dim=512",
    ])
    fabric = Fabric(devices=1, accelerator="cpu", precision="bf16-mixed")
    venv = VectorJaxEnv(JaxMultiRoom(), B)
    actions_dim, is_continuous = spaces_to_dims(venv.single_action_space)
    agent, params = build_agent(fabric, actions_dim, is_continuous, cfg, venv.single_observation_space)
    read = anakin.read_obs_fn(("rgb",), venv.single_observation_space)
    rollout_fn = anakin.make_rollout_fn(
        venv, agent.apply, lambda out, k: sample_actions(out, actions_dim, is_continuous, k),
        cnn_keys=("rgb",), mlp_keys=(), action_space=venv.single_action_space, gamma=0.99, rollout_steps=T,
    )

    def program(p, actor, key):
        k_roll, k_perm = jax.random.split(key)
        actor, rollout, _, _ = rollout_fn(p, actor, k_roll)
        pool = rollout["rgb"].reshape((T * B,) + rollout["rgb"].shape[2:])
        _, values = agent.apply(p, read({"rgb": pool}))
        idx = jax.random.permutation(k_perm, T * B)[:minibatch]

        def loss(p):
            out, new_values = agent.apply(p, read({"rgb": jnp.take(pool, idx, axis=0)}))
            return jnp.mean((new_values - jnp.take(values, idx, axis=0)) ** 2) + jnp.mean(out[0] * rollout["actions"][0, 0, 0])

        return actor, values, jax.grad(loss)(p)

    def specs(sharding):
        put = lambda x: _spec(sharding, *x.shape, dtype=x.dtype)  # noqa: E731
        key = jax.random.PRNGKey(0)
        env_state = jax.eval_shape(lambda k: venv.reset(k)[0], key)
        actor = {
            "env": env_state, "ep_ret": jax.ShapeDtypeStruct((B,), jnp.float32),
            "ep_len": jax.ShapeDtypeStruct((B,), jnp.int32), "update": jax.ShapeDtypeStruct((), jnp.int32),
        }
        return jax.tree.map(put, (params, actor, key))

    return program, specs


def test_pixel_rollout_stores_uint8_and_nothing_re_lays_a_float_pool_on_v5e(one_chip):
    """At the Anakin cell's shapes: it compiles; no float32 array of the pool's element count stands
    between two kernels; no ``copy`` of one step's frames is left in the scan; and the temporaries
    (4.57 GB, 34.1 GB accessed) are under the float store's: on the parent of PR 33 (commit 2328e5c,
    the reader an identity) this program is refused, its stacked ``bf16[128,512,64,64,3]`` tiled with
    the last axis of 3 in the lanes wanting 68.7 GB, and the whole ``ppo.anakin_phase`` took 12.90 GB
    with 69.4 GB accessed where it now takes 5.92 GB with 35.5 (CHANGES.md, PR 33)."""
    program, specs = pixel_rollout_and_update()
    compiled = jax.jit(program).lower(*specs(one_chip)).compile()
    step, pool = 512 * 12288, 128 * 512 * 12288
    standing = set(_unfused_instructions(compiled.as_text()))
    assert ("while", "u8", pool) in standing  # the scan stacks bytes
    assert not [x for x in standing if x[1] == "f32" and x[2] == pool]
    assert not [x for x in standing if x[0] == "copy" and x[2] == step]
    assert 0 < compiled.memory_analysis().temp_size_in_bytes < 8 * 2**30


def decode_step_of_32_envs(model_name, vocab, sharding):
    """``(decoder config, jitted step with the carry donated, its argument shapes on ``sharding``)`` at a token
    cell's cut: ``configs/algo/decoder/<model_name>.yaml``, bf16, caches of 8,192 positions."""
    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.models import decoder

    model = compose(["exp=ppo_tokens", f"algo/decoder@algo.decoder={model_name}"]).as_dict()["algo"]["decoder"]
    dc = decoder.DecoderConfig.from_dict(model, vocab_size=vocab, max_len=8192)
    on_chip = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)  # noqa: E731
    params = on_chip(jax.eval_shape(lambda k: jax.tree.map(lambda x: x.astype(jnp.bfloat16), decoder.init_params(dc, k)), jax.random.PRNGKey(0)))
    carry = on_chip(jax.eval_shape(lambda: decoder.init_carry(dc, 32)))
    step = jax.jit(lambda p, c, tok, first: decoder.step(p, dc, c, tok, first, jnp.bfloat16), donate_argnums=(1,))
    return dc, step, (params, carry, _spec(sharding, 32, dtype=jnp.int32), _spec(sharding, 32))


def test_hybrid_decode_step_compiles_for_v5e_at_lfm2_widths_with_a_window_and_no_cache_for_a_conv_layer(one_chip):
    """One decode step of 32 envs through the LFM2 cut (``configs/algo/decoder/lfm2_24b.yaml``, bf16): it compiles
    for the chip, the carry it takes is one full cache (keys and values of 8,192 positions, a slot's 8 heads of 64
    side by side in 512 lanes) and four windows of two rows, 16.8 MB an env, and what it hands back is as large
    (nothing of a conv layer grows with the episode)."""
    from sheeprl_tpu.models import decoder

    dc, step, args = decode_step_of_32_envs("lfm2_24b", 8192, one_chip)
    carry = args[1]
    assert [x.shape for x in carry["conv"]] == [(32, 2, 2048)] * 4 and [x.shape for x in carry["k"]] == [(32, 8192, 512)]
    compiled = step.lower(*args).compile()
    carry_bytes = 32 * sum(decoder.carry_bytes(dc).values())
    assert carry_bytes == 32 * (2 * 8192 * 8 * 64 * 2 + 4 * 2 * 2048 * 2 + 4)
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= carry_bytes - 32 * 4 - 4 * 32 * 2 * 2048 * 2  # the cache is updated in place
    assert ma.temp_size_in_bytes < 2**30


@pytest.mark.parametrize("model, vocab, caches", [("lfm2_24b", 8192, [8192]), ("trinity_mini", 25024, [2048] * 4 + [8192])],
                         ids=["lfm2_cut", "trinity_cut"])
def test_decode_step_reads_its_caches_through_the_ragged_kernel_and_copies_none_on_v5e(one_chip, monkeypatch, model, vocab, caches):
    """One decode step of 32 envs at a token cell's cut, lowered as the chip lowers it (the kernel asks
    ``jax.default_backend()`` whether Mosaic is there, and this process holds the CPU): every attention layer
    (the full cache of 8,192 and, in Trinity, the four rings of 2,048: the dense layer has one too) is one ``decode_attention`` kernel; no
    copy, transpose or fusion output of a whole cache's shape stands in memory beside the in-place write of the
    token's row; the caches are aliased; the temporaries stay under 1 GiB (7 to 8 MB)."""
    from sheeprl_tpu.models import decoder

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dc, step, args = decode_step_of_32_envs(model, vocab, one_chip)
    assert [x.shape for x in args[1]["k"]] == [(32, size, 512) for size in caches]
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines() if "custom-call(" in line and "decode_attention" in line]
    assert len(kernels) == len(caches)
    whole = {32 * size * 512 for size in caches}
    standing = [x for x in _unfused_instructions(text) if x[2] in whole and x[0] in ("copy", "transpose", "fusion")]
    assert not standing, standing
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= 2 * 2 * 32 * 512 * sum(caches)  # keys and values, bf16, updated in place
    assert 0 < ma.temp_size_in_bytes < 2**30


def nemotron3_cut(sharding):
    """``(decoder config, float32 parameter shapes, the same as the rollout reads them, a carry of 32 envs)`` at the
    Nemotron-3 cell's cut (``configs/algo/decoder/nemotron3_nano.yaml``, 16,384 ids, caches of 8,192 positions)."""
    from sheeprl_tpu.algos.ppo_recurrent.agent import DecoderPPOAgent
    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.models import decoder

    model = compose(["exp=ppo_tokens", "algo/decoder@algo.decoder=nemotron3_nano"]).as_dict()["algo"]["decoder"]
    dc = decoder.DecoderConfig.from_dict(model, vocab_size=16384, max_len=8192)
    on_chip = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)  # noqa: E731
    agent = DecoderPPOAgent(dc, ("tokens",), jnp.bfloat16)
    params = jax.eval_shape(lambda k: decoder.init_params(dc, k), jax.random.PRNGKey(0))
    return dc, on_chip(params), on_chip(jax.eval_shape(agent.acting_params, params)), on_chip(jax.eval_shape(lambda: agent.initial_state(32)))


def test_state_space_decode_step_compiles_for_v5e_at_nemotron3_widths_and_copies_neither_cache_nor_state(one_chip, monkeypatch):
    """One decode step of 32 envs through the Nemotron-3 cut, bf16, lowered as the chip lowers it: it compiles; the one
    attention layer's cache of 8,192 rows of 256 lanes (2 heads of 128, 16 queries a key head: a third shape for
    ``decode_attention``) goes through the kernel and no copy, transpose or fusion output of the cache's shape stands
    beside it; every Mamba-2 layer's state ``f32[32,64,64,128]`` is made by ONE fusion, which also gives the state's
    read (the update and ``S C`` share one pass over the state), written into the carry's own donated buffer: the step
    holds no buffer of the state's shape beside the carry's four; what sets the decays stays float32."""
    from sheeprl_tpu.models import decoder

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dc, _, acting, carry = nemotron3_cut(one_chip)
    assert [x.shape for x in carry["k"]] == [(32, 8192, 256)] and [(x.shape, x.dtype) for x in carry["ssm"]] == [((32, 64, 64, 128), jnp.float32)] * 4
    assert [x.shape for x in carry["ssm_window"]] == [(32, 3, 6144)] * 4
    assert {acting["layer_0"][k].dtype for k in decoder.FLOAT32_LEAVES} == {jnp.dtype(jnp.float32)} and acting["layer_0"]["w_in"].dtype == jnp.bfloat16
    step = jax.jit(lambda p, c, tok, first: decoder.step(p, dc, c, tok, first, jnp.bfloat16), donate_argnums=(1,))
    compiled = step.lower(acting, carry, _spec(one_chip, 32, dtype=jnp.int32), _spec(one_chip, 32)).compile()
    text = compiled.as_text()
    assert len([line for line in text.splitlines() if "custom-call(" in line and "decode_attention" in line]) == 1
    standing = list(_unfused_instructions(text))
    cache, state = 32 * 8192 * 256, 32 * 64 * 64 * 128
    assert not [x for x in standing if x[2] == cache and x[0] in ("copy", "transpose", "fusion")]
    passed_on = ("parameter", "get-tuple-element", "tuple", "bitcast")  # these make no buffer
    states = [x[0] for x in standing if x[1] == "f32" and x[2] == state and x[0] not in passed_on]
    assert states == ["fusion"] * 4, states  # one an SSM layer, and no copy of a state
    ma = compiled.memory_analysis()
    carry_bytes = 32 * sum(decoder.carry_bytes(dc).values())
    assert carry_bytes == 32 * 16924676 and ma.alias_size_in_bytes >= carry_bytes - 32 * 4  # cache, states and windows in place
    assert 0 < ma.temp_size_in_bytes < 2 * 4 * state  # 90 MB, 80 of them one expert layer's `w1` re-laid for the grouped product


def test_state_space_update_compiles_for_v5e_at_nemotron3_widths(one_chip):
    """One update of the Nemotron-3 cut at a minibatch's size (8 envs x 256 tokens from a carried state, bf16 compute,
    float32 parameters): the loss's gradient through the chunked scan, the prefix read and the grouped product compiles
    for the chip within what the phase has room for (the compiler counts 1.43 GB of temporaries)."""
    from sheeprl_tpu.models import decoder

    dc, params, _, carry = nemotron3_cut(one_chip)
    carry = jax.tree.map(lambda x: jax.ShapeDtypeStruct((8,) + x.shape[1:], x.dtype, sharding=one_chip), carry)

    def loss(p, c, tokens, first):
        logits, values, load = decoder.segment(p, dc, c, tokens, first, jnp.bfloat16)
        return jnp.mean(jax.nn.logsumexp(logits, -1)) + jnp.mean(values ** 2), load

    compiled = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        params, carry, _spec(one_chip, 256, 8, dtype=jnp.int32), _spec(one_chip, 256, 8)).compile()
    assert 0 < compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30


def test_expert_layer_compiles_for_v5e_at_nemotron3_widths_as_one_product_a_matrix_with_every_result_cut_to_the_held_rows(one_chip):
    """The expert layer's loss and gradient at the Nemotron-3 cut's widths (2,048 tokens, 6 of 128 experts a token, 8
    held: 12,288 sorted (token, expert) pairs) compile for the chip as one grouped product a matrix and direction (two
    forward, two to the rows, two to the matrices: ``relu^2``), no conditional and no loop around them (no second
    program for an overflow: the products' time follows the rows in their groups), within the parent's temporaries
    (228 MB there by the same compile), and a select of the rows' shape follows each product that gives rows."""
    import re

    from sheeprl_tpu.models import decoder

    dc, params, _, _ = nemotron3_cut(one_chip)
    rows = 2048 * dc.num_experts_per_tok

    def loss(moe, m):
        experts, weights, _ = decoder.route(moe, m, dc)
        return jnp.sum(decoder.held_experts(moe["experts"], m, experts, weights, dc).astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params["layer_1"]["moe"], _spec(one_chip, 2048, dc.hidden_size, dtype=jnp.bfloat16)).compile()
    text = compiled.as_text()
    products = re.findall(r"= bf16\[(\d+),[\d,]+\]\S* custom-call\(.*ragged-dot-none", text)
    assert sorted(int(n) for n in products) == [dc.experts_held[1]] * 2 + [rows] * 4, products
    assert " conditional(" not in text and " while(" not in text
    assert re.search(rf"pred\[{rows},{dc.moe_intermediate_size}\]", text) and re.search(rf"pred\[{rows},{dc.hidden_size}\]", text)  # the cuts
    assert 0 < compiled.memory_analysis().temp_size_in_bytes < 240 * 2**20


def keye_cut(sharding, envs):
    """``(decoder config, float32 parameter shapes, the same as the rollout reads them, a carry of ``envs`` envs)`` at
    the Keye-VL-2.0 cell's cut (``configs/algo/decoder/keye_vl2.yaml``, 18,992 ids, caches of 32,768 positions)."""
    from sheeprl_tpu.algos.ppo_recurrent.agent import DecoderPPOAgent
    from sheeprl_tpu.config.compose import compose
    from sheeprl_tpu.models import decoder

    model = compose(["exp=ppo_tokens", "algo/decoder@algo.decoder=keye_vl2"]).as_dict()["algo"]["decoder"]
    dc = decoder.DecoderConfig.from_dict(model, vocab_size=18992, max_len=32768)
    on_chip = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)  # noqa: E731
    agent = DecoderPPOAgent(dc, ("tokens",), jnp.bfloat16)
    params = jax.eval_shape(lambda k: decoder.init_params(dc, k), jax.random.PRNGKey(0))
    return dc, on_chip(params), on_chip(jax.eval_shape(agent.acting_params, params)), on_chip(jax.eval_shape(lambda: agent.initial_state(envs)))


def test_sparse_decode_step_compiles_for_v5e_at_keye_widths_and_fetches_only_the_selected_rows(one_chip):
    """One decode step of 8 envs through the Keye-VL-2.0 cut, bf16: it compiles for the chip; each of the four sparse
    layers writes its token's key, value and index key into the carry's own donated buffers, scores the index keys,
    and fetches 2,048 rows of its key and value caches by a gather: no copy, transpose or fusion output of a whole
    ``(8, 32768, 512)`` cache stands beside the carry's own, and every such cache is aliased.  (The selection is
    XLA's ``top_k``: a sort of the 32,768 scores of each env.)"""
    import re

    from sheeprl_tpu.models import decoder

    dc, _, acting, carry = keye_cut(one_chip, 8)
    assert [x.shape for x in carry["k"]] == [(8, 32768, 512)] * 4 and [x.shape for x in carry["ik"]] == [(8, 32768, 64)] * 4
    carry_bytes = 8 * sum(decoder.carry_bytes(dc).values())
    assert carry_bytes == 8 * (4 * 32768 * (512 + 512 + 64) * 2 + 4)  # 285.2 MB an env
    step = jax.jit(lambda p, c, tok, first: decoder.step(p, dc, c, tok, first, jnp.bfloat16), donate_argnums=(1,))
    compiled = step.lower(acting, carry, _spec(one_chip, 8, dtype=jnp.int32), _spec(one_chip, 8)).compile()
    text = compiled.as_text()
    whole = 8 * 32768 * 512
    standing = list(_unfused_instructions(text))
    assert not [x for x in standing if x[2] == whole and x[0] in ("copy", "transpose", "fusion", "gather")], standing
    fetched = re.findall(r"= bf16\[8,2048,512\]\S* gather\(", text)
    assert len(fetched) == 2 * 4, fetched  # keys and values of each layer: the selected rows and no others
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= carry_bytes - 8 * 4  # caches and index keys in place
    assert 0 < ma.temp_size_in_bytes < 2**30


def test_sparse_update_and_prefill_compile_for_v5e_at_keye_widths(one_chip, monkeypatch):
    """One update of the Keye-VL-2.0 cut at a minibatch's size (4 envs x 256 tokens on a carried prefix of 32,768
    positions, bf16 compute, float32 parameters), lowered as the chip lowers it (the segment kernels ask
    ``jax.default_backend()`` whether Mosaic is there): the loss with L_I and its gradient, the selection made once a
    layer outside the recomputed layers, each sparse layer's prefix read by the ``segment_attention`` kernels (the
    forward, its ``dq`` and the heads' mean for L_I: in the update's program the forward runs again under the layer's
    checkpoint), compiles for the chip within what the phase has room for; and the prefill of 256 tokens for 8 envs,
    whose layers run the forward kernel alone (the last layer's attention gives only logits, which the prefill drops)."""
    from sheeprl_tpu.models import decoder

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dc, params, acting, carry8 = keye_cut(one_chip, 8)
    carry = jax.tree.map(lambda x: jax.ShapeDtypeStruct((4,) + x.shape[1:], x.dtype, sharding=one_chip), carry8)

    def loss(p, c, tokens, first):
        logits, values, load, kl = decoder.segment(p, dc, c, tokens, first, jnp.bfloat16, index_loss=True)
        return jnp.mean(jax.nn.logsumexp(logits, -1)) + jnp.mean(values ** 2) + jnp.mean(kl), load

    compiled = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        params, carry, _spec(one_chip, 256, 4, dtype=jnp.int32), _spec(one_chip, 256, 4)).compile()
    update_temp = compiled.memory_analysis().temp_size_in_bytes
    def kernels(text):  # the custom calls by kernel, from their instructions' names: forward, dq, the heads' mean
        names = [line.split(" = ")[0] for line in text.splitlines() if "custom-call(" in line]
        count = lambda *parts: sum(all(p in n for p in parts) for n in names)  # noqa: E731
        return count("segment_attention") - count("segment_attention_dq"), count("segment_attention_dq"), count("segment_head_mean")

    forward, dq, head_mean = kernels(compiled.as_text())
    assert forward >= 4 and dq == 4 and head_mean >= 4
    prefill = jax.jit(lambda p, c, tok, n: decoder.segment(p, dc, c, tok, jnp.zeros(tok.shape), jnp.bfloat16, extend=True, valid=n)[3],
                      donate_argnums=(1,))
    compiled = prefill.lower(acting, carry8, _spec(one_chip, 256, 8, dtype=jnp.int32), _spec(one_chip, 8, dtype=jnp.int32)).compile()
    prefill_temp = compiled.memory_analysis().temp_size_in_bytes
    assert kernels(compiled.as_text()) == (3, 0, 0)  # the last layer's attention reaches nothing the prefill keeps
    print(f"update temporaries {update_temp / 1e9:.2f} GB, prefill {prefill_temp / 1e9:.2f} GB")
    assert 0 < update_temp < 5 * 2**30 and 0 < prefill_temp < 5 * 2**30
