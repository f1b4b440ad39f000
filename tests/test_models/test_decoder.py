"""The decoder core against the plain reference (``chipbench/reference/trinity_mini_ep8.py``, which imports
nothing from the program) at tiny widths on the CPU: hidden 64, 4 query heads on 2 key-value heads of 16,
window 8, 8 experts of width 32 with 2 a token, vocabulary 64."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import decoder
from sheeprl_tpu.models.decoder import DecoderConfig
from sheeprl_tpu.ops import decode_attention

ROOT = Path(__file__).resolve().parents[2]
LAYERS = ("sliding_attention",) * 4 + ("full_attention",)
TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, sliding_window=8,
    intermediate_size=128, moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, experts_held=(0, 2),
    num_shared_experts=1, num_dense_layers=1, layer_types=LAYERS, rms_norm_eps=1e-5, rope_theta=10000.0,
    route_scale=2.826, route_norm=True, mup_enabled=True, load_balance_coeff=0.001,
)
VOCAB, MAX_LEN = 64, 32
TOL = dict(rtol=2e-4, atol=2e-4)
# caches of two of the kernel's blocks: every attention layer then takes the ragged read of ops/decode_attention.py
# (the rings too: with a window this long a sliding layer sees the whole of these short episodes)
TWO_BLOCKS = 2 * decode_attention.BLOCK
CACHES = pytest.mark.parametrize("caches", [MAX_LEN, TWO_BLOCKS], ids=["plain", "ragged"])


def load_reference():
    spec = importlib.util.spec_from_file_location("trinity_reference", ROOT / "chipbench/reference/trinity_mini_ep8.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()


@pytest.fixture(autouse=True)
def query_blocks_of_four(monkeypatch):
    """A segment of these tests then spans several query blocks, as a rollout's does at the published sizes."""
    monkeypatch.setattr(decoder, "Q_BLOCK", 4)


def config(max_len=MAX_LEN, **changes):
    return DecoderConfig.from_dict({**TINY, **changes}, vocab_size=VOCAB, max_len=max_len)


def config_with(caches):
    """The tiny model with the tests' own caches (rings of 8, a cache of 32), or with rings of two blocks and a
    cache of four (a ring wraps before the cache is full)."""
    return config() if caches == MAX_LEN else config(max_len=2 * caches, sliding_window=caches)


def ref_config(cfg: DecoderConfig):
    return ref._Static({**TINY, "experts_held": cfg.experts_held, "sliding_window": cfg.sliding_window})


def mid_episode(carry, pos, seed=9):
    """``carry`` as if every env stood at ``pos`` of an episode: random keys and values (constants to whatever
    reads them, as a cache written under older parameters is), so that steps cross a block's end and a ring's."""
    fill = lambda i, z: jax.random.normal(jax.random.PRNGKey(seed + i), z.shape, z.dtype)  # noqa: E731
    return {**carry, "k": [fill(2 * i, z) for i, z in enumerate(carry["k"])],
            "v": [fill(2 * i + 1, z) for i, z in enumerate(carry["v"])], "pos": jnp.asarray(pos, jnp.int32)}


def episode(seed, T, B, resets=()):
    """Tokens (T, B) and is_first (T, B): a reset at step 0 and at every (t, b) of ``resets``."""
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (T, B), 0, VOCAB)
    first = np.zeros((T, B), np.float32)
    first[0] = 1.0
    for t, b in resets:
        first[t, b] = 1.0
    return tokens, jnp.asarray(first)


def reference_full(params, cfg, tokens, first, **how):
    """The reference's full forward over whole episodes from nothing: (logits, values) as (T, B, ...)."""
    T, B = tokens.shape
    pos, ep = ref.positions(first, jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32))
    logits, values, counts, _ = ref.forward(params, dict(ref_config(cfg)), tokens.T, pos.T, ep.T, ref.empty_past(TINY, B), **how)
    return jnp.moveaxis(logits, 0, 1), values.T, counts


@pytest.fixture(scope="module")
def params():
    return decoder.init_params(config(), jax.random.PRNGKey(0), std=0.1)


@pytest.mark.parametrize("resets", [(), ((5, 0), (11, 1), (12, 1))], ids=["past_the_window", "resets_inside"])
def test_segment_forward_matches_the_reference(params, resets):
    """Episodes longer than the window (8), with and without a reset inside the segment: logits and values."""
    cfg = config()
    tokens, first = episode(1, 20, 3, resets)
    logits, values, load = decoder.segment(params, cfg, decoder.init_carry(cfg, 3, jnp.float32), tokens, first, jnp.float32)
    want_logits, want_values, want_load = reference_full(params, cfg, tokens, first)
    np.testing.assert_allclose(logits, want_logits, **TOL)
    np.testing.assert_allclose(values[..., 0], want_values, **TOL)
    np.testing.assert_array_equal(load, want_load)


def test_a_window_layer_differs_from_a_full_one_past_the_window(params):
    """The reference's planted fault (a sliding layer that sees the whole episode) must move the result, or
    the comparison above would not hold the window to anything."""
    cfg = config()
    tokens, first = episode(1, 20, 3)
    sound, _, _ = reference_full(params, cfg, tokens, first)
    faulty, _, _ = reference_full(params, cfg, tokens, first, fault="window")
    np.testing.assert_allclose(sound[:8], faulty[:8], **TOL)
    assert float(jnp.abs(sound[8:] - faulty[8:]).max()) > 1e-2


@CACHES
def test_steps_through_the_cache_match_one_segment(params, caches):
    """T calls of ``step`` against one ``segment`` call on the same tokens, a reset inside included.  With caches
    of two blocks the envs stand mid-episode: one crosses a block's end, one the ring's, one is reset."""
    cfg = config_with(caches)
    tokens, first = episode(2, 20, 3, ((7, 2), (13, 0)))
    carry, end = decoder.init_carry(cfg, 3, jnp.float32), [7, 20, 13]
    if caches == TWO_BLOCKS:
        first = first.at[0, :2].set(0.0)  # envs 0 and 1 go on with the episode the carry holds
        carry, end = mid_episode(carry, [decode_attention.BLOCK - 9, TWO_BLOCKS - 6, 40]), [7, TWO_BLOCKS + 14, 13]
    want_logits, want_values, _ = decoder.segment(params, cfg, carry, tokens, first, jnp.float32)
    step = jax.jit(lambda c, tok, f: decoder.step(params, cfg, c, tok, f, jnp.float32))
    for t in range(tokens.shape[0]):
        carry, logits, value = step(carry, tokens[t], first[t])
        np.testing.assert_allclose(logits, want_logits[t], **TOL)
        np.testing.assert_allclose(value, want_values[t], **TOL)
    assert carry["pos"].tolist() == end


@pytest.mark.parametrize("valid, caches", [(None, MAX_LEN), ((8, 3, 0), MAX_LEN), ((8, 3, 0), TWO_BLOCKS)],
                         ids=["whole", "ragged", "ragged_prefill_ragged_caches"])
def test_prefill_then_decode_then_the_full_pass_agree(params, valid, caches):
    """A segment on a cached prefix against the same tokens in one piece: prefill 8 tokens (ragged: only each
    env's first ``valid``), decode 4 through the cache, run the next 8 as a segment on that cache; all of it
    against the reference's full forward of every env's own tokens."""
    cfg = config_with(caches)
    B = 3
    tokens, first = episode(3, 20, B)
    n = np.asarray(valid if valid is not None else (8,) * B)
    carry = decoder.init_carry(cfg, B, jnp.float32)
    _, _, _, carry = decoder.segment(
        params, cfg, carry, tokens[:8], first[:8], jnp.float32, extend=True, valid=None if valid is None else jnp.asarray(n))
    assert carry["pos"].tolist() == n.tolist()
    # every env goes on from where its prefill ended: env b's stream is its first n_b tokens, then tokens[8:]
    streams = [np.concatenate([np.asarray(tokens[: n[b], b]), np.asarray(tokens[8:, b])]) for b in range(B)]
    got = []
    for t in range(8, 12):
        carry, logits, _ = decoder.step(params, cfg, carry, tokens[t], jnp.where(carry["pos"] == 0, 1.0, 0.0), jnp.float32)
        got.append(logits)
    seg_first = jnp.zeros((8, B)).at[0].set(jnp.where(carry["pos"] == 0, 1.0, 0.0))
    logits, _, _ = decoder.segment(params, cfg, carry, tokens[12:], seg_first, jnp.float32)
    got = jnp.concatenate([jnp.stack(got), logits])  # (12, B, V): the last 12 tokens of every stream
    for b in range(B):
        stream = jnp.asarray(streams[b])[:, None]
        want, _, _ = reference_full(params, cfg, stream, jnp.zeros(stream.shape).at[0].set(1.0))
        np.testing.assert_allclose(got[:, b], want[-12:, 0], **TOL)


@CACHES
def test_the_counts_of_what_the_decode_steps_fetched(caches):
    """``cache_read`` and ``cache_held`` as the agent counts them from a rollout's positions: equal where every
    layer is read whole, the closed form (whole blocks up to each env's length) where the layers are ragged."""
    from sheeprl_tpu.algos.ppo_recurrent.agent import DecoderPPOAgent

    cfg = config_with(caches)
    agent = DecoderPPOAgent(cfg, ("tokens",))
    block = decode_attention.BLOCK
    pos = np.asarray([[0, block - 2, block - 1, block, caches - 1, caches, 2 * caches - 1, 7]] * 2)  # (T, B)
    counts = agent.cache_counts(pos.size, int(agent.cache_blocks(jnp.asarray(pos))))
    sizes = [cfg.cache_len(i) for i in range(len(LAYERS))]
    assert counts["cache_held"] == pos.size * sum(sizes)
    if caches == MAX_LEN:
        assert agent.ragged_sizes == [] and counts["cache_read"] == counts["cache_held"]
    else:
        whole_blocks = lambda n: -(-n // block) * block  # noqa: E731
        want = sum(whole_blocks(min(int(p) + 1, size)) for size in sizes for p in pos.reshape(-1))
        assert agent.ragged_sizes == sizes and counts["cache_read"] == want < counts["cache_held"]


def moe_layer(params):
    return params["layer_1"]["moe"]


def test_the_shares_add_up_to_the_whole_layer():
    """8 experts split 2 a share over 4 shares: the four partial results of the routed experts, and the shared
    expert counted once, add up to the uncut reference's layer output; the router's counts sum to k x tokens
    whatever is held."""
    whole = decoder.init_params(config(experts_held=(0, 8)), jax.random.PRNGKey(4), std=0.1)
    moe = moe_layer(whole)
    m = jax.random.normal(jax.random.PRNGKey(5), (24, 64))
    want, want_counts = ref.experts_part(moe, m, {**TINY, "experts_held": (0, 8)}, "f32", None)
    total = decoder._ffn(moe["shared"], m)
    for first in range(0, 8, 2):
        cfg = config(experts_held=(first, 2))
        experts, weights, counts = decoder.route(moe, m, cfg)
        assert int(counts.sum()) == 2 * 24
        np.testing.assert_array_equal(counts, want_counts)
        share = {k: v[first:first + 2] for k, v in moe["experts"].items()}
        total = total + decoder.held_experts(share, m, experts, weights, cfg)
    np.testing.assert_allclose(total, want, **TOL)


@pytest.mark.parametrize("tokens", [24, 4], ids=["a_segment_s_rows", "a_decode_step_s_rows"])
@pytest.mark.parametrize("bias, all_here", [((9.0, 0, 0, 0, 0, 0, 0, 8.0), True), ((0, 0, -9.0, -9.0, 0, 0, 0, 0), False)],
                         ids=["all_to_one_held_expert", "none_to_a_held_expert"])
def test_routing_is_dropless_at_the_extremes(bias, all_here, tokens):
    """Every token sent to one held expert (and to one held elsewhere), and no token to any held expert: the
    reference's result both times, no token dropped."""
    cfg = config(experts_held=(0, 2) if all_here else (2, 2))
    layer = decoder.init_params(cfg, jax.random.PRNGKey(6), std=0.1)["layer_1"]
    moe = dict(layer["moe"], router_bias=jnp.asarray(bias, jnp.float32))
    m = jax.random.normal(jax.random.PRNGKey(7), (tokens, 64))
    experts, weights, counts = decoder.route(moe, m, cfg)
    held = counts[cfg.experts_held[0]: cfg.experts_held[0] + 2]
    assert int(held.max()) == (tokens if all_here else 0) and int(counts.sum()) == 2 * tokens
    got = decoder._ffn(moe["shared"], m) + decoder.held_experts(moe["experts"], m, experts, weights, cfg)
    want, _ = ref.experts_part(moe, m, {**TINY, "experts_held": cfg.experts_held}, "f32", None)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("experts", [8, 16], ids=["3_of_8_held", "3_of_16_held"])
def test_gradients_through_the_grouped_product_match_the_dense_loop(experts):
    """Rows of experts held elsewhere belong to no group of the grouped product; nothing of them may reach the
    gradients (on a TPU such rows are left unwritten, so the layer cuts them off on both sides of every product).
    A held range that is not the first experts."""
    cfg = config(experts_held=(2, 3), num_experts=experts)
    moe = decoder.init_params(cfg, jax.random.PRNGKey(8), std=0.1)["layer_1"]["moe"]
    m = jax.random.normal(jax.random.PRNGKey(9), (24, 64))

    def ours(moe, m):
        experts, weights, _ = decoder.route(moe, m, cfg)
        return jnp.sum(jnp.sin(decoder._ffn(moe["shared"], m) + decoder.held_experts(moe["experts"], m, experts, weights, cfg)))

    def theirs(moe, m):
        return jnp.sum(jnp.sin(ref.experts_part(moe, m, {**TINY, "experts_held": (2, 3), "num_experts": experts}, "f32", None)[0]))

    got, want = jax.grad(ours, argnums=(0, 1))(moe, m), jax.grad(theirs, argnums=(0, 1))(moe, m)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, **TOL)


def dense_loop(w, m, experts, weights, cfg):
    """Every held expert over every token, weighted by the token's gate for it (nought where it was not chosen)."""
    first, held = cfg.experts_held
    out = jnp.zeros(m.shape, jnp.float32)
    for e in range(held):
        gate = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=1)
        out = out + decoder._ffn({name: mat[e] for name, mat in w.items()}, m) * gate[:, None]
    return out


def pairs_routed_here(n, how, tokens, cfg):
    """Experts (tokens, k) with ``n`` of the ``tokens * k`` pairs routed to held experts (``how``: spread over them,
    or all to ``one``) and the others to experts held elsewhere."""
    first, held = cfg.experts_held
    k = cfg.num_experts_per_tok
    elsewhere = np.asarray([e for e in range(cfg.num_experts) if not first <= e < first + held])
    flat = elsewhere[np.arange(tokens * k) % len(elsewhere)]
    here = np.random.default_rng(n).permutation(tokens * k)[:n]
    flat[here] = first + (np.arange(n) % held if how == "spread" else held - 1)
    return jnp.asarray(flat.reshape(tokens, k), jnp.int32)


@pytest.mark.parametrize("act", ["silu_gated", "relu2"])
@pytest.mark.parametrize("held", [(0, 3), (5, 3)], ids=["the_first_experts", "experts_5_to_7"])
@pytest.mark.parametrize("n, how", [(12, "spread"), (0, "spread"), (24, "spread"), (25, "spread"), (24, "one"), (48, "one"), (48, "spread")],
                         ids=["an_even_router", "none_here", "half_the_pairs", "half_and_one", "half_to_one_expert",
                              "every_pair_to_one_held_expert", "every_pair_here"])
def test_the_held_experts_part_equals_the_dense_loop_whatever_share_is_routed_here(act, held, n, how):
    """24 tokens' 48 sorted pairs, 3 of 16 experts held, ``n`` of the pairs routed to them: the grouped products run
    over those ``n`` rows alone (the others lie in no group) and the part equals the dense loop, forward and in the
    gradients to the matrices, the rows and the gates, for silu-gated experts and for ``relu^2`` ones; with no pair
    routed here it is nought and so is every gradient."""
    cfg = config(experts_held=held, num_experts=16, ffn_act=act)
    w = decoder.init_params(cfg, jax.random.PRNGKey(11), std=0.1)["layer_1"]["moe"]["experts"]
    assert set(w) == ({"w1", "w2"} if act == "relu2" else {"w1", "w2", "w3"})
    m = jax.random.normal(jax.random.PRNGKey(12), (24, 64))
    weights = jax.random.uniform(jax.random.PRNGKey(13), (24, 2), jnp.float32, 0.1, 1.0)
    experts = pairs_routed_here(n, how, 24, cfg)
    mix = jax.random.normal(jax.random.PRNGKey(14), (24, 64))
    run = lambda layer: jax.value_and_grad(lambda w, m, weights: jnp.sum(layer(w, m, experts, weights, cfg) * mix), argnums=(0, 1, 2))(w, m, weights)  # noqa: E731
    got, want = run(decoder.held_experts), run(dense_loop)
    for g, d in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, d, **TOL)
    if n == 0:
        assert float(got[0]) == 0.0 and all(not np.asarray(g).any() for g in jax.tree.leaves(got[1]))


def test_the_rows_counted_as_run_are_the_held_pairs_in_whole_tiles(monkeypatch):
    """``rows_run`` (what the dispatch counts for ``moe.rows_run_pct``): the pairs routed to the held experts, the rows
    ``held_experts`` puts into groups, rounded up to the grouped product's tiles and never more than all rows."""
    cfg = config(experts_held=(2, 3), num_experts=16)
    monkeypatch.setattr(decoder, "ROW_TILE", 8)
    for n, rows in ((0, 0), (1, 8), (8, 8), (9, 16), (44, 48), (48, 48)):
        counts = jnp.zeros((16,), jnp.int32).at[pairs_routed_here(n, "spread", 24, cfg).reshape(-1)].add(1)
        assert int(counts[2:5].sum()) == n and int(decoder.rows_run(counts, 48, cfg)) == rows
    stacked = jnp.stack([jnp.zeros((16,), jnp.int32).at[2].set(24), jnp.zeros((16,), jnp.int32).at[3].set(25).at[9].set(7)])
    assert decoder.rows_run(stacked, 48, cfg).tolist() == [24, 32]  # a row of counts an expert layer
    monkeypatch.setattr(decoder, "ROW_TILE", 512)
    assert decoder.rows_run(stacked, 48, cfg).tolist() == [48, 48] and int(decoder.rows_run(stacked[0] * 0, 48, cfg)) == 0


def test_the_bias_rule_over_one_update(params):
    cfg = config()
    load = jnp.asarray(np.random.default_rng(0).integers(0, 9, (4, 8)))
    got = decoder.update_router_bias(params, load, cfg)
    want = ref.bias_step(params, load, TINY)
    for i in cfg.moe_layers():
        row = load[i - 1].astype(jnp.float32)
        np.testing.assert_allclose(got[f"layer_{i}"]["moe"]["router_bias"], 0.001 * jnp.sign(row.mean() - row))
        np.testing.assert_array_equal(got[f"layer_{i}"]["moe"]["router_bias"], want[f"layer_{i}"]["moe"]["router_bias"])
    assert got["layer_0"] is params["layer_0"]


def test_parameter_count_at_the_published_widths():
    """The configuration's table (chipbench/configs/trinity_mini_ep8.json) from the shapes ``init_params`` makes."""
    import json

    from sheeprl_tpu.config.compose import compose

    model = compose(["exp=ppo_tokens"]).as_dict()["algo"]["decoder"]
    cfg = DecoderConfig.from_dict(model, vocab_size=25024, max_len=8192)
    shapes = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))  # noqa: E731
    stated = json.loads((ROOT / "chipbench/configs/trinity_mini_ep8.json").read_text())["parameters"]
    assert count(shapes["layer_0"]) == stated["dense layer"]
    assert count(shapes["layer_1"]) == stated["expert layer (16 of 128 experts held)"]
    assert count(shapes) == stated["total"] == 705476352


# ----------------------------------------------------------------------------
# the hybrid: gated short convolutions beside full attention (configs/algo/decoder/tiny_hybrid.yaml) against
# chipbench/reference/lfm2_24b_ep8.py
# ----------------------------------------------------------------------------

def load_hybrid_reference():
    spec = importlib.util.spec_from_file_location("lfm2_reference", ROOT / "chipbench/reference/lfm2_24b_ep8.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


href = load_hybrid_reference()


def hybrid_model(**changes):
    from sheeprl_tpu.config.compose import compose

    model = compose(["exp=ppo_tokens", "algo/decoder@algo.decoder=tiny_hybrid"]).as_dict()["algo"]["decoder"]
    return {**model, **changes}


def hybrid_config(max_len=MAX_LEN, **changes):
    return DecoderConfig.from_dict(hybrid_model(**changes), vocab_size=VOCAB, max_len=max_len)


def hybrid_ref_config(**changes):
    model = hybrid_model(**changes)
    return href._Static({**model, "layer_types": tuple(model["layer_types"]), "experts_held": tuple(model["experts_held"])})


@pytest.fixture(scope="module")
def hybrid_params():
    params = decoder.init_params(hybrid_config(), jax.random.PRNGKey(10), std=0.1)
    for i in (0, 2, 3, 4):  # taps large enough that a tap cut in the wrong place moves the result
        params[f"layer_{i}"]["conv_w"] = params[f"layer_{i}"]["conv_w"] * 10.0
    return params


def hybrid_reference_full(params, tokens, first, **how):
    """The hybrid reference's full forward over whole episodes from nothing: (logits, values) as (T, B, ...)."""
    T, B = tokens.shape
    cfg = dict(hybrid_ref_config())
    pos, ep = href.positions(first, jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32))
    logits, values, counts, _ = href.forward(params, cfg, tokens.T, pos.T, ep.T, href.empty_past(cfg, B), **how)
    return jnp.moveaxis(logits, 0, 1), values.T, counts


def test_the_hybrid_carry_holds_two_kinds_of_state():
    cfg = hybrid_config()
    carry = decoder.init_carry(cfg, 3, jnp.float32)
    assert [x.shape for x in carry["k"]] == [(3, MAX_LEN, 2 * 16)] and len(carry["v"]) == 1  # a slot's heads side by side
    assert [x.shape for x in carry["conv"]] == [(3, 2, 64)] * 4
    assert decoder.carry_bytes(cfg, jnp.bfloat16) == {"pos": 4, "full_attention": 2 * MAX_LEN * 2 * 16 * 2, "conv": 4 * 2 * 64 * 2}
    assert "conv" not in decoder.init_carry(config(), 3)  # a model without the kind has no entry for it


@pytest.mark.parametrize("resets", [(), ((5, 0), (11, 1), (12, 1), (14, 1))], ids=["one_episode", "resets_inside"])
def test_hybrid_segment_matches_the_reference(hybrid_params, resets):
    """A segment from nothing, with and without resets inside it (two one step apart: an episode of one token,
    whose successor has one live tap): logits, values and the router's counts."""
    cfg = hybrid_config()
    tokens, first = episode(11, 20, 3, resets)
    logits, values, load = decoder.segment(hybrid_params, cfg, decoder.init_carry(cfg, 3, jnp.float32), tokens, first, jnp.float32)
    want_logits, want_values, want_load = hybrid_reference_full(hybrid_params, tokens, first)
    np.testing.assert_allclose(logits, want_logits, **TOL)
    np.testing.assert_allclose(values[..., 0], want_values, **TOL)
    np.testing.assert_array_equal(load, want_load)


@pytest.mark.parametrize("fault", ["conv_prefix", "conv_reset"])
def test_the_hybrid_reference_s_planted_faults_move_the_result(hybrid_params, fault):
    """Taps that reach across an episode's start, and taps that read nought where the past's rows belong, must
    each move the result where they apply and nowhere else, or the comparisons here hold the taps to nothing."""
    tokens, first = episode(11, 20, 3, ((5, 0),))
    code = href.FAULT_CODES[fault]
    sound, _, _ = hybrid_reference_full(hybrid_params, tokens, first)
    if fault == "conv_reset":
        faulty, _, _ = hybrid_reference_full(hybrid_params, tokens, first, fault_code=code)
        np.testing.assert_allclose(sound[:, 1:], faulty[:, 1:], **TOL)  # envs without a reset inside
        np.testing.assert_allclose(sound[:5, 0], faulty[:5, 0], **TOL)
        assert float(jnp.abs(sound[5:7, 0] - faulty[5:7, 0]).max()) > 1e-2
    else:  # the second half on the first half as its past: the fault cuts what the past gives the first two tokens
        cfg = dict(hybrid_ref_config())
        pos, ep = (z.T for z in href.positions(first, jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32)))
        made = href.forward(hybrid_params, cfg, tokens.T[:, :10], pos[:, :10], ep[:, :10], href.empty_past(cfg, 3))[3]
        past = {"layers": made, "pos": pos[:, :10], "ep": ep[:, :10]}
        on_past = lambda c: href.forward(hybrid_params, cfg, tokens.T[:, 10:], pos[:, 10:], ep[:, 10:], past, fault_code=c)[0]  # noqa: E731
        np.testing.assert_allclose(on_past(0), jnp.moveaxis(sound, 0, 1)[:, 10:], **TOL)
        assert float(jnp.abs(on_past(code)[:, :2] - on_past(0)[:, :2]).max()) > 1e-2


@CACHES
def test_hybrid_steps_through_the_carry_match_one_segment(hybrid_params, caches):
    """T calls of ``step`` (a window shifted, a slot written) against one ``segment`` call, resets inside.  With a
    cache of two blocks env 1 stands mid-episode and crosses a block's end."""
    cfg = hybrid_config(max_len=caches)
    tokens, first = episode(12, 20, 3, ((7, 2), (8, 2), (13, 0)))
    carry, end = decoder.init_carry(cfg, 3, jnp.float32), [7, 20, 12]
    if caches == TWO_BLOCKS:
        first = first.at[0, 1].set(0.0)
        carry, end = mid_episode(carry, [3, decode_attention.BLOCK - 9, 40]), [7, decode_attention.BLOCK + 11, 12]
    want_logits, want_values, _ = decoder.segment(hybrid_params, cfg, carry, tokens, first, jnp.float32)
    step = jax.jit(lambda c, tok, f: decoder.step(hybrid_params, cfg, c, tok, f, jnp.float32))
    for t in range(tokens.shape[0]):
        carry, logits, value = step(carry, tokens[t], first[t])
        np.testing.assert_allclose(logits, want_logits[t], **TOL)
        np.testing.assert_allclose(value, want_values[t], **TOL)
    assert carry["pos"].tolist() == end and set(carry) == {"k", "v", "conv", "pos"}


@pytest.mark.parametrize("valid, caches", [(None, MAX_LEN), ((8, 2, 1, 0), MAX_LEN), ((8, 2, 1, 0), TWO_BLOCKS)],
                         ids=["whole", "ragged", "ragged_prefill_ragged_cache"])
def test_hybrid_prefill_then_decode_then_the_full_pass_agree(hybrid_params, valid, caches):
    """Prefill 8 tokens (ragged: each env's first ``valid``: 0, 1, 2 and more real tokens), decode 4 through
    the carry, run the next 8 as a segment on that carry: the logits of all 12 against the reference's full
    forward of every env's own tokens.  The window left behind is the gated input of the last two REAL tokens."""
    cfg = hybrid_config(max_len=caches)
    B = 4
    tokens, first = episode(13, 20, B)
    n = np.asarray(valid if valid is not None else (8,) * B)
    carry = decoder.init_carry(cfg, B, jnp.float32)
    _, _, _, carry = decoder.segment(
        hybrid_params, cfg, carry, tokens[:8], first[:8], jnp.float32, extend=True, valid=None if valid is None else jnp.asarray(n))
    assert carry["pos"].tolist() == n.tolist()
    streams = [np.concatenate([np.asarray(tokens[: n[b], b]), np.asarray(tokens[8:, b])]) for b in range(B)]
    got = []
    for t in range(8, 12):
        carry, logits, _ = decoder.step(hybrid_params, cfg, carry, tokens[t], jnp.where(carry["pos"] == 0, 1.0, 0.0), jnp.float32)
        got.append(logits)
    seg_first = jnp.zeros((8, B)).at[0].set(jnp.where(carry["pos"] == 0, 1.0, 0.0))
    logits, _, _ = decoder.segment(hybrid_params, cfg, carry, tokens[12:], seg_first, jnp.float32)
    got = jnp.concatenate([jnp.stack(got), logits])  # (12, B, V): the last 12 tokens of every stream
    for b in range(B):
        stream = jnp.asarray(streams[b])[:, None]
        want, _, _ = hybrid_reference_full(hybrid_params, stream, jnp.zeros(stream.shape).at[0].set(1.0))
        np.testing.assert_allclose(got[:, b], want[-12:, 0], **TOL)


def test_hybrid_gradients_match_the_reference(hybrid_params):
    """Gradients of a function of logits and values through conv taps, resets and the grouped product."""
    cfg = hybrid_config()
    tokens, first = episode(14, 12, 2, ((5, 0),))
    carry = decoder.init_carry(cfg, 2, jnp.float32)

    def ours(p):
        logits, values, _ = decoder.segment(p, cfg, carry, tokens, first, jnp.float32)
        return jnp.sum(jnp.sin(logits)) + jnp.sum(values ** 2)

    def theirs(p):
        logits, values, _ = hybrid_reference_full(p, tokens, first)
        return jnp.sum(jnp.sin(logits)) + jnp.sum(values ** 2)

    got, want = jax.grad(ours)(hybrid_params), jax.grad(theirs)(hybrid_params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-4, err_msg=jax.tree_util.keystr(path))


def test_the_hybrid_shares_add_up_to_the_whole_layer():
    """8 experts split 2 a share over 4 shares, no shared expert: the four partial results add up to the uncut
    reference's layer output."""
    whole = decoder.init_params(hybrid_config(experts_held=(0, 8)), jax.random.PRNGKey(4), std=0.1)
    moe = whole["layer_1"]["moe"]
    assert "shared" not in moe
    m = jax.random.normal(jax.random.PRNGKey(5), (24, 64))
    want, want_counts = href.experts_part(moe, m, dict(hybrid_ref_config(experts_held=(0, 8))), "f32")
    total = 0.0
    for first in range(0, 8, 2):
        cfg = hybrid_config(experts_held=(first, 2))
        experts, weights, counts = decoder.route(moe, m, cfg)
        np.testing.assert_array_equal(counts, want_counts)
        np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-5)  # norm_topk_prob, routed_scaling_factor 1
        share = {k: v[first:first + 2] for k, v in moe["experts"].items()}
        total = total + decoder.held_experts(share, m, experts, weights, cfg)
    np.testing.assert_allclose(total, want, **TOL)


@pytest.mark.parametrize("cut", [True, False], ids=["one_chip_s_cut", "the_uncut_model"])
def test_lfm2_parameter_count_at_the_published_widths(cut):
    """The configuration's table (chipbench/configs/lfm2_24b_ep8.json) from the shapes ``init_params`` makes, and
    the whole model (40 layers, 2 of them dense, 64 experts held, the whole vocabulary) within 1% of its 24.0 B."""
    import json

    from sheeprl_tpu.config.compose import compose

    model = compose(["exp=ppo_tokens", "algo/decoder@algo.decoder=lfm2_24b"]).as_dict()["algo"]["decoder"]
    file = json.loads((ROOT / "chipbench/configs/lfm2_24b_ep8.json").read_text())
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))  # noqa: E731
    if cut:
        cfg = DecoderConfig.from_dict(model, vocab_size=8192, max_len=8192)
        shapes = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.PRNGKey(0))
        stated = file["parameters"]
        assert count(shapes["layer_0"]) == stated["layer 0: conv mixer, dense feed-forward"] == 89139200
        assert count(shapes["layer_1"]) == stated["attention expert layer (8 of 64 experts held)"] == 86118592
        assert [count(shapes[f"layer_{i}"]) for i in (2, 3, 4)] == [stated["conv expert layer (8 of 64 experts held)"]] * 3 == [92416064] * 3
        assert count(shapes) == stated["total"] == 486064512
    else:
        whole = dict(model, layer_types=file["published"]["layer_types_list"], num_dense_layers=2, experts_held=[0, 64])
        cfg = DecoderConfig.from_dict(whole, vocab_size=65536, max_len=8192)
        assert cfg.layer_types.count("conv") == 30 and cfg.layer_types.count("full_attention") == 10
        shapes = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.PRNGKey(0))
        assert abs(count(shapes) / 24.0e9 - 1.0) < 0.01, count(shapes)
