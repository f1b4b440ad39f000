"""The decoder's state-space layers against the plain reference (``chipbench/reference/nemotron3_nano_ep16.py``,
which imports nothing from the program and runs the Mamba-2 layer as the sequential recurrence) at tiny widths on
the CPU: ``configs/algo/decoder/tiny_ssm.yaml``: hidden 64, 4 Mamba-2 heads of 16 on 2 groups of state 8, 4 taps,
scan chunks of 4; 4 query heads on 2 key-value heads of 16; 8 relu^2 experts of width 32 with 2 a token and a shared
expert of 48; every layer ONE part."""

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import decoder
from sheeprl_tpu.models.decoder import DecoderConfig

ROOT = Path(__file__).resolve().parents[2]
VOCAB, MAX_LEN = 64, 32
TOL = dict(rtol=2e-4, atol=2e-4)
PATTERN = {"M": "mamba2", "E": "moe", "*": "full_attention"}


def load_reference():
    spec = importlib.util.spec_from_file_location("nemotron3_reference", ROOT / "chipbench/reference/nemotron3_nano_ep16.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()


def model(name="tiny_ssm", **changes):
    from sheeprl_tpu.config.compose import compose

    return {**compose(["exp=ppo_tokens", f"algo/decoder@algo.decoder={name}"]).as_dict()["algo"]["decoder"], **changes}


def config(max_len=MAX_LEN, **changes):
    return DecoderConfig.from_dict(model(**changes), vocab_size=VOCAB, max_len=max_len)


def ref_config(**changes):
    m = model(**changes)
    return ref._Static({**m, "layer_types": tuple(m["layer_types"]), "experts_held": tuple(m["experts_held"])})


@pytest.fixture(scope="module")
def params():
    """Seeded weights, the skip ``D`` raised on the last two heads: the gated norm's two groups (heads 0 and 1, heads
    2 and 3) then differ in mean square, so a norm over all 64 channels gives another result."""
    p = decoder.init_params(config(), jax.random.PRNGKey(0))
    for i in config().layers_of(decoder.MAMBA):
        p[f"layer_{i}"]["D"] = jnp.asarray([1.0, 1.0, 5.0, 5.0])
    return p


def tokens_of(seed, T, B):
    return jax.random.randint(jax.random.PRNGKey(seed), (T, B), 0, VOCAB)


def firsts(T, B, resets):
    first = np.zeros((T, B), np.float32)
    for t, b in resets:
        first[t, b] = 1.0
    return jnp.asarray(first)


def mid_episode(params, B, history=8, seed=20):
    """Every env ``history`` tokens into an episode: the program's carry, prefilled through the chunked scan (two
    chunks), and the reference's past of the same tokens (every column's keys and values; the state and the last
    three convolution inputs where the env stands).  Returns (carry, past)."""
    cfg, rcfg = config(), dict(ref_config())
    tokens = tokens_of(seed, history, B)
    first = firsts(history, B, [(0, b) for b in range(B)])
    carry = decoder.segment(params, cfg, decoder.init_carry(cfg, B, jnp.float32), tokens, first, jnp.float32, extend=True)[3]
    pos, ep = (z.T for z in ref.positions(first, jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32)))
    made = ref.forward(params, rcfg, tokens.T, pos, ep - 1, ref.empty_past(rcfg, B))[3]  # episode 0: the carry's
    return carry, {"layers": made, "pos": pos, "ep": ep - 1}


def reference_on(params, past, tokens, first, pos0, **how):
    """The reference over a segment on ``past``: logits (T, B, V), values (T, B), counts, what the layers made."""
    B = tokens.shape[1]
    pos, ep = ref.positions(first, jnp.asarray(pos0, jnp.int32), jnp.zeros((B,), jnp.int32))
    logits, values, counts, made = ref.forward(params, dict(ref_config()), tokens.T, pos.T, ep.T, past, **how)
    return jnp.moveaxis(logits, 0, 1), values.T, counts, made


def states_of(made):
    kinds = config().layer_types
    return [m for m, kind in zip(made, kinds) if kind == decoder.MAMBA]


RESETS = {
    "none": (),
    "inside_a_chunk": ((5, 0), (6, 1)),
    "at_a_chunk_s_first_token": ((8, 0), (4, 2)),
    "at_the_segment_s_first_token": ((0, 1), (0, 2)),
    "all_of_them": ((0, 0), (5, 0), (8, 0), (3, 1), (4, 1), (7, 2), (8, 2), (9, 2)),
}


def test_the_carry_holds_three_kinds_of_state_and_the_state_is_float32():
    cfg = config()
    carry = decoder.init_carry(cfg, 3)  # bf16 carry
    assert [x.shape for x in carry["k"]] == [(3, MAX_LEN, 2 * 16)] and "conv" not in carry
    assert [(x.shape, x.dtype) for x in carry["ssm"]] == [((3, 4, 16, 8), jnp.float32)] * 4
    assert [(x.shape, x.dtype) for x in carry["ssm_window"]] == [((3, 3, 64 + 2 * 2 * 8), jnp.bfloat16)] * 4
    assert decoder.carry_bytes(cfg, jnp.bfloat16) == {
        "pos": 4, "full_attention": 2 * MAX_LEN * 2 * 16 * 2, "mamba2": 4 * (4 * 16 * 8 * 4 + 3 * 96 * 2)}
    assert cfg.moe_layers() == (1, 3, 6, 8) and [cfg.ffn_of(i) for i in (0, 1, 5)] == [None, "moe", None]


@pytest.mark.parametrize("resets", sorted(RESETS))
def test_steps_one_segment_and_the_recurrence_agree(params, resets):
    """From a carry eight tokens into an episode (prefilled through the chunked scan): 12 calls of ``step``, one
    ``segment`` of three chunks, and the reference's sequential recurrence on the same past: logits, values, the
    router's counts and the state and window every Mamba-2 layer is left with.  Resets inside a chunk, at a chunk's
    first token, at the segment's first token (the carry's state is cut there), several an env, and none."""
    cfg, B, T = config(), 3, 12
    carry, past = mid_episode(params, B)
    tokens, first = tokens_of(21, T, B), firsts(T, B, RESETS[resets])
    want_logits, want_values, want_load, made = reference_on(params, past, tokens, first, carry["pos"])
    logits, values, load, after = decoder.segment(params, cfg, carry, tokens, first, jnp.float32, extend=True)
    np.testing.assert_allclose(logits, want_logits, **TOL)
    np.testing.assert_allclose(values[..., 0], want_values, **TOL)
    np.testing.assert_array_equal(load, want_load)
    step = jax.jit(lambda c, tok, f: decoder.step(params, cfg, c, tok, f, jnp.float32))
    stepped = carry
    for t in range(T):
        stepped, step_logits, step_value = step(stepped, tokens[t], first[t])
        np.testing.assert_allclose(step_logits, want_logits[t], **TOL)
        np.testing.assert_allclose(step_value[..., 0], want_values[t], **TOL)
    assert set(stepped) == {"k", "v", "ssm", "ssm_window", "pos"} and stepped["pos"].tolist() == after["pos"].tolist()
    for i, (state, window) in enumerate(states_of(made)):
        assert float(jnp.abs(state).max()) > 1e-2  # a state worth comparing
        for got in (after, stepped):
            np.testing.assert_allclose(got["ssm"][i], state, rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(got["ssm_window"][i], window, **TOL)


def test_a_norm_over_all_channels_or_one_group_of_b_and_c_would_not_pass(params, monkeypatch):
    """What holds the comparison above to the grouped norm and to the groups of ``B`` and ``C``: a gated norm over
    all 64 channels, and the reference's planted fault (every head reads group 0), each move the logits."""
    cfg, B, T = config(), 3, 12
    carry, past = mid_episode(params, B)
    tokens, first = tokens_of(21, T, B), firsts(T, B, ())
    want, _, _, _ = reference_on(params, past, tokens, first, carry["pos"])
    one_group, _, _, _ = reference_on(params, past, tokens, first, carry["pos"], fault_code=ref.FAULT_CODES["one_bc_group"])
    assert float(jnp.abs(one_group - want).max()) > 1e-2

    def over_all_channels(layer, y, z, dc):
        g = y * jax.nn.silu(z.astype(jnp.float32))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + dc.rms_norm_eps) * layer["norm_gate"]
        return g.astype(z.dtype) @ layer["w_out"].astype(z.dtype)

    monkeypatch.setattr(decoder, "_ssm_output", over_all_channels)
    jax.clear_caches()  # the checkpointed layer may have been traced with the real norm by a test before this one
    try:
        logits, _, _ = decoder.segment(params, cfg, carry, tokens, first, jnp.float32)
    finally:
        jax.clear_caches()  # and no later test may find this one's trace
    assert float(jnp.abs(logits - want).max()) > 1e-2


@pytest.mark.parametrize("fault, moved", [("ssm_reset", "after_the_reset"), ("ssm_prefix", "from_the_first_token")])
def test_the_reference_s_planted_state_faults_move_the_result(params, fault, moved):
    """A state that is not cut at an episode's start, and a segment that starts from nought where the past's state
    belongs, must each move the result where they apply and nowhere else."""
    B, T = 3, 12
    carry, past = mid_episode(params, B)
    tokens, first = tokens_of(21, T, B), firsts(T, B, ((5, 0),))
    sound, _, _, _ = reference_on(params, past, tokens, first, carry["pos"])
    faulty, _, _, _ = reference_on(params, past, tokens, first, carry["pos"], fault_code=ref.FAULT_CODES[fault])
    if fault == "ssm_reset":
        np.testing.assert_allclose(sound[:, 1:], faulty[:, 1:], **TOL)  # envs without a reset inside
        np.testing.assert_allclose(sound[:5, 0], faulty[:5, 0], **TOL)
        assert float(jnp.abs(sound[5:, 0] - faulty[5:, 0]).max()) > 1e-2
    else:
        assert float(jnp.abs(sound[0] - faulty[0]).max()) > 1e-2


def test_gradients_of_a_masked_loss_through_the_chunked_scan_match_the_recurrence(params):
    """The gradient of a masked function of logits and values through ``segment`` (chunked scan from the carry's
    state, a reset inside a chunk and one at a chunk's edge) against the reference's through the sequential scan."""
    cfg, B, T = config(), 2, 12
    carry, past = mid_episode(params, B)
    tokens, first = tokens_of(22, T, B), firsts(T, B, ((5, 0), (8, 1)))
    mask = (jax.random.uniform(jax.random.PRNGKey(23), (T, B)) < 0.7).astype(jnp.float32)

    def ours(p):
        logits, values, _ = decoder.segment(p, cfg, carry, tokens, first, jnp.float32)
        return jnp.sum(mask * (jnp.sum(jnp.sin(logits), -1) + values[..., 0] ** 2))

    def theirs(p):
        logits, values, _, _ = reference_on(p, past, tokens, first, carry["pos"])
        return jnp.sum(mask * (jnp.sum(jnp.sin(logits), -1) + values ** 2))

    got, want = jax.grad(ours)(params), jax.grad(theirs)(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-4, err_msg=jax.tree_util.keystr(path))
    for i in cfg.layers_of(decoder.MAMBA):  # the scan's own leaves take a gradient worth comparing
        assert all(float(jnp.abs(want[f"layer_{i}"][k]).max()) > 1e-3 for k in ("A_log", "dt_bias", "D", "conv_w", "conv_b"))


def test_a_ragged_prefill_leaves_the_state_and_window_of_each_env_s_last_real_token(params):
    """Prefill 8 tokens of which only each env's first ``valid`` are real (8, 3, 1 and none), on a carry that is
    mid-episode: state and window are those after the last real token (the env with none keeps the carry's bit for
    bit), and four decode steps from there agree with the reference on each env's own stream."""
    cfg, B = config(), 4
    carry, past = mid_episode(params, B)
    tokens, n = tokens_of(24, 12, B), np.asarray([8, 3, 1, 0])
    none = firsts(8, B, ())
    after = decoder.segment(params, cfg, carry, tokens[:8], none, jnp.float32, extend=True, valid=jnp.asarray(n))[3]
    assert after["pos"].tolist() == (np.asarray(carry["pos"]) + n).tolist()
    pos, _ = ref.positions(none, carry["pos"], jnp.zeros((B,), jnp.int32))
    ep = jnp.where(jnp.arange(8)[None] < n[:, None], 0, -1)  # what is not real is padding to the reference
    made = ref.forward(params, dict(ref_config()), tokens[:8].T, pos.T, ep, past)[3]
    for i, (state, window) in enumerate(states_of(made)):
        np.testing.assert_allclose(after["ssm"][i], state, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(after["ssm_window"][i], window, **TOL)
        np.testing.assert_array_equal(after["ssm"][i][3], carry["ssm"][i][3])
        np.testing.assert_array_equal(after["ssm_window"][i][3], carry["ssm_window"][i][3])
    for b in range(B):  # env b's stream: its first n_b tokens of the prefill, then tokens[8:]
        stream = jnp.concatenate([tokens[: n[b], b], tokens[8:, b]])[:, None]
        one = jax.tree.map(lambda z: z[b:b + 1], past)
        want, _, _, _ = reference_on(params, one, stream, firsts(stream.shape[0], 1, ()), carry["pos"][b:b + 1])
        stepped = jax.tree.map(lambda z: z[b:b + 1], after)
        for t in range(8, 12):
            stepped, logits, _ = decoder.step(params, cfg, stepped, tokens[t, b:b + 1], jnp.zeros((1,)), jnp.float32)
            np.testing.assert_allclose(logits[0], want[n[b] + t - 8, 0], **TOL)


def test_a_segment_that_does_not_divide_into_chunks_is_refused(params):
    cfg = config()
    with pytest.raises(ValueError, match="scan chunks"):
        decoder.segment(params, cfg, decoder.init_carry(cfg, 2, jnp.float32), tokens_of(1, 6, 2), firsts(6, 2, ()), jnp.float32)


def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """16 experts split 1 a share over 16 shares (the deployment's sixteen chips): the sixteen partial results of the
    routed experts, and the shared expert (its own width, every chip computes it alike) counted once, add up to the
    uncut reference's layer output; every share routes over all 16 and counts alike."""
    sizes = dict(num_experts=16, num_experts_per_tok=6)
    whole = decoder.init_params(config(experts_held=(0, 16), **sizes), jax.random.PRNGKey(4))
    moe = whole["layer_1"]["moe"]
    assert set(moe["experts"]) == {"w1", "w2"} and moe["shared"]["w1"].shape == (64, 48)  # two matrices; its own width
    m = jax.random.normal(jax.random.PRNGKey(5), (24, 64))
    want, want_counts = ref.experts_part(moe, m, dict(ref_config(experts_held=(0, 16), **sizes)), "f32")
    total = decoder._ffn(moe["shared"], m)
    for first in range(16):
        cfg = config(experts_held=(first, 1), **sizes)
        experts, weights, counts = decoder.route(moe, m, cfg)
        np.testing.assert_array_equal(counts, want_counts)
        np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)  # norm_topk_prob, routed_scaling_factor 2.5
        share = {k: v[first:first + 1] for k, v in moe["experts"].items()}
        total = total + decoder.held_experts(share, m, experts, weights, cfg)
    np.testing.assert_allclose(total, want, **TOL)
    assert float(jnp.abs(want - decoder._ffn(moe["shared"], m)).max()) > 1e-2  # the routed experts' part is worth adding


@pytest.mark.parametrize("cut", [True, False], ids=["one_chip_s_cut", "the_uncut_model"])
def test_nemotron3_parameter_count_at_the_published_widths(cut):
    """The configuration's table (chipbench/configs/nemotron3_nano_ep16.json) from the shapes ``init_params`` makes,
    leaf by leaf, and the whole model (52 layers, 128 experts held, the whole vocabulary) within 1% of its 31.6 B."""
    published = model("nemotron3_nano")
    file = json.loads((ROOT / "chipbench/configs/nemotron3_nano_ep16.json").read_text())
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))  # noqa: E731
    if not cut:
        letters = file["published"]["hybrid_override_pattern"]
        whole = dict(published, layer_types=[PATTERN[c] for c in letters], experts_held=[0, file["published"]["n_routed_experts"]])
        cfg = DecoderConfig.from_dict(whole, vocab_size=file["published"]["vocab_size"], max_len=8192)
        assert [cfg.layer_types.count(k) for k in ("mamba2", "moe", "full_attention")] == [23, 23, 6]
        total = count(jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.PRNGKey(0)))
        assert total == 31577942976 and abs(total / 31.6e9 - 1.0) < 0.01
        return
    cfg = DecoderConfig.from_dict(published, vocab_size=file["vocab_size"], max_len=8192)
    assert list(cfg.layer_types) == [PATTERN[c] for c in file["hybrid_override_pattern"]] and cfg.experts_held == (0, file["n_routed_experts"])
    shapes = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.PRNGKey(0))
    leaves = lambda i: {k: tuple(v.shape) for k, v in shapes[f"layer_{i}"].items() if k != "moe"}  # noqa: E731
    assert leaves(0) == {
        "norm_in": (2688,), "w_in": (2688, 4096 + 6144 + 64), "conv_w": (4, 6144), "conv_b": (6144,), "dt_bias": (64,),
        "A_log": (64,), "D": (64,), "norm_gate": (4096,), "w_out": (4096, 2688)}
    assert leaves(5) == {"norm_in": (2688,), "wq": (2688, 4096), "wk": (2688, 256), "wv": (2688, 256), "wo": (4096, 2688)}
    assert leaves(1) == {"norm_pre_mlp": (2688,)} and jax.tree.map(lambda x: tuple(x.shape), shapes["layer_1"]["moe"]) == {
        "router": (2688, 128), "router_bias": (128,), "shared": {"w1": (2688, 3712), "w2": (3712, 2688)},
        "experts": {"w1": (8, 2688, 1856), "w2": (8, 1856, 2688)}}
    stated = file["parameters"]
    assert [count(shapes[f"layer_{i}"]) for i in (0, 2, 4, 7)] == [stated["Mamba-2 layer"]] * 4 == [38744896] * 4
    assert count(shapes["layer_5"]) == stated["attention layer"] == 23399040
    assert [count(shapes[f"layer_{i}"]) for i in (1, 3, 6, 8)] == [stated["expert layer (8 of 128 experts held)"]] * 4 == [100125440] * 4
    outside = count({k: v for k, v in shapes.items() if not k.startswith("layer_")})
    assert outside == stated["embedding, head, value head, final norm"] == 88085760
    assert count(shapes) == stated["total"] == 666966144
    carry = decoder.carry_bytes(cfg)
    assert carry == {"pos": 4, "full_attention": 2 * 8192 * 256 * 2, "mamba2": 4 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)} and sum(carry.values()) == 16924676


def test_the_seeded_state_space_leaves_are_the_family_s():
    """``A_log = log(1 .. heads)``, ``D`` ones, zero conv bias, taps within ``+-1 / sqrt(K)``, and ``softplus(dt_bias)``
    inside [``ssm_dt_min``, ``ssm_dt_max``]."""
    cfg = config()
    layer = decoder.init_params(cfg, jax.random.PRNGKey(3))["layer_0"]
    np.testing.assert_allclose(layer["A_log"], np.log(np.arange(1, 5)), rtol=1e-6)
    assert layer["D"].tolist() == [1.0] * 4 and not layer["conv_b"].any()
    assert float(jnp.abs(layer["conv_w"]).max()) <= 0.5 and float(layer["conv_w"].std()) > 0.2
    dt = jax.nn.softplus(layer["dt_bias"])
    assert float(dt.min()) >= 0.001 * 0.999 and float(dt.max()) <= 0.1 * 1.001


@pytest.mark.parametrize("bad", [dict(mixer_ffn=True), dict(ssm_heads=0), dict(ssm_groups=3), dict(ffn_act="gelu")],
                         ids=["a_moe_layer_beside_a_mixer_s_own", "no_heads", "groups_that_do_not_divide", "unknown_form"])
def test_a_yaml_that_states_an_impossible_layer_is_refused(bad):
    with pytest.raises(ValueError):
        config(**bad)
