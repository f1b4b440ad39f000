"""The decoder's learned sparse attention layers against the plain reference (``chipbench/reference/keye_vl2_ep16.py``,
which imports nothing from the program, selects by sorting every token's index scores in float32 and attends under a
dense mask) at tiny widths on the CPU: ``configs/algo/decoder/tiny_sparse.yaml``: hidden 64, 4 query heads on 2
key-value heads of 16, an indexer of 2 heads of 8 on one key head that keeps the 6 best keys, 8 silu-gated experts of
width 32 with 2 a token under a softmax router, no shared expert; two layers, each attention then experts."""

import importlib.util
import json
from unittest import mock
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import decoder
from sheeprl_tpu.models.decoder import DecoderConfig
from sheeprl_tpu.ops import segment_attention

ROOT = Path(__file__).resolve().parents[2]
VOCAB, MAX_LEN, TOPK = 64, 32, 6
TOL = dict(rtol=2e-4, atol=2e-4)


def load_reference():
    spec = importlib.util.spec_from_file_location("keye_reference", ROOT / "chipbench/reference/keye_vl2_ep16.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()


def model(name="tiny_sparse", **changes):
    from sheeprl_tpu.config.compose import compose

    return {**compose(["exp=ppo_tokens", f"algo/decoder@algo.decoder={name}"]).as_dict()["algo"]["decoder"], **changes}


def config(max_len=MAX_LEN, **changes):
    return DecoderConfig.from_dict(model(**changes), vocab_size=VOCAB, max_len=max_len)


def ref_config(**changes):
    m = model(**changes)
    return ref._Static({**m, "layer_types": tuple(m["layer_types"]), "experts_held": tuple(m["experts_held"])})


@pytest.fixture(scope="module")
def params():
    return decoder.init_params(config(), jax.random.PRNGKey(0))


def tokens_of(seed, T, B):
    return jax.random.randint(jax.random.PRNGKey(seed), (T, B), 0, VOCAB)


def firsts(T, B, resets):
    first = np.zeros((T, B), np.float32)
    for t, b in resets:
        first[t, b] = 1.0
    return jnp.asarray(first)


def mid_episode(params, valid, seed=20):
    """Every env ``valid[b]`` tokens into an episode, prefilled in one ragged segment of 12 (``valid`` real tokens an
    env): the program's carry, and the reference's past of the same tokens (every column's keys, values and index
    keys; the columns that are not real are padding).  Returns (carry, past)."""
    cfg, rcfg = config(), dict(ref_config())
    B, T = len(valid), 12
    n = np.asarray(valid)
    tokens = tokens_of(seed, T, B)
    first = firsts(T, B, ())
    carry = decoder.segment(params, cfg, decoder.init_carry(cfg, B, jnp.float32), tokens, first, jnp.float32,
                            extend=True, valid=jnp.asarray(n))[3]
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    ep = jnp.where(pos < n[:, None], 0, -1)
    made = ref.forward(params, rcfg, tokens.T, pos, ep, ref.empty_past(rcfg, B))[3]
    return carry, {"layers": made, "pos": pos, "ep": ep}


def reference_on(params, past, tokens, first, pos0, **how):
    """The reference over a segment on ``past``: logits (T, B, V), values (T, B), what the layers made, L_I (T, B),
    each layer's selection as positions (B, T, MAX_LEN) bool, and the segment's positions (T, B)."""
    B, T = tokens.shape[1], tokens.shape[0]
    pos, ep = ref.positions(first, jnp.asarray(pos0, jnp.int32), jnp.zeros((B,), jnp.int32))
    logits, values, _, made, kl, sel = ref.forward(params, dict(ref_config()), tokens.T, pos.T, ep.T, past, **how)
    cols = np.concatenate([np.asarray(past["pos"]), np.asarray(pos.T)], axis=1)
    chosen = []
    for s in sel:
        mask = np.zeros((B, T, MAX_LEN), bool)
        b, t, c = np.nonzero(np.asarray(s))
        mask[b, t, cols[b, c]] = True
        chosen.append(mask)
    return jnp.moveaxis(logits, 0, 1), values.T, made, kl.T, chosen, pos


RESETS = {"none": (), "inside_the_segment": ((5, 0), (9, 2)), "at_its_first_token": ((0, 1),)}


def test_the_carry_holds_index_keys_beside_keys_and_values():
    cfg = config()
    carry = decoder.init_carry(cfg, 3)  # bf16 carry
    assert [x.shape for x in carry["k"]] == [(3, MAX_LEN, 2 * 16)] * 2 and [x.shape for x in carry["ik"]] == [(3, MAX_LEN, 8)] * 2
    assert decoder.carry_bytes(cfg, jnp.bfloat16) == {"pos": 4, "sparse_attention": 2 * MAX_LEN * (2 * 2 * 16 + 8) * 2}
    layer = decoder.init_params(cfg, jax.random.PRNGKey(1))["layer_0"]
    assert jax.tree.map(lambda x: x.shape, layer["index"]) == {"wq": (64, 16), "wk": (64, 8), "norm": (8,), "norm_bias": (8,), "ww": (64, 2)}
    assert set(layer["moe"]) == {"router", "experts"}  # a softmax router: no selection bias, no shared expert
    assert cfg.carry_slot(1) == 1 and cfg.moe_layers() == (0, 1)


@pytest.mark.parametrize("resets", sorted(RESETS))
def test_steps_one_segment_and_the_reference_agree(params, resets):
    """From a carry 11, 7 and 9 tokens into its episodes (a ragged prefill): 12 calls of ``step``, one ``segment`` and
    the reference on the same past agree on logits, values and the carry's index keys, and step by step the selected
    positions are the reference's; most steps select a strict subset of their episode."""
    cfg, B, T = config(), 3, 12
    carry, past = mid_episode(params, [11, 7, 9])
    tokens, first = tokens_of(21, T, B), firsts(T, B, RESETS[resets])
    logits, values, _ = decoder.segment(params, cfg, carry, tokens, first, jnp.float32)
    whole = decoder.segment(params, cfg, carry, tokens, first, jnp.float32, extend=True)[3]
    want, want_v, made, _, chosen, pos = reference_on(params, past, tokens, first, carry["pos"])
    np.testing.assert_allclose(logits, want, **TOL)
    np.testing.assert_allclose(values[..., 0], want_v, **TOL)
    stepped, strict = carry, 0
    for t in range(T):
        rows = []
        stepped, lg, v = decoder.step(params, cfg, stepped, tokens[t], first[t], jnp.float32, rows)
        np.testing.assert_allclose(lg, want[t], **TOL)
        np.testing.assert_allclose(v[:, 0], want_v[t], **TOL)
        for layer, slots in enumerate(rows):
            got = np.zeros((B, MAX_LEN), bool)
            for b in range(B):
                got[b, [s for s in np.asarray(slots[b]).tolist() if s >= 0]] = True
            np.testing.assert_array_equal(got, chosen[layer][:, t])
        strict += int(np.sum(np.asarray(pos[t]) + 1 > TOPK))
    assert strict >= T  # the selection bites
    for layer in range(2):  # the index keys the steps wrote, those the segment wrote, and the reference's
        for b in range(B):
            slots = np.asarray(pos[:, b])
            np.testing.assert_allclose(stepped["ik"][layer][b, slots], made[layer][2][b], **TOL)
            np.testing.assert_allclose(whole["ik"][layer][b, slots], made[layer][2][b], **TOL)
    np.testing.assert_array_equal(stepped["pos"], whole["pos"])


@pytest.mark.parametrize("how", ["scores", "ties"])
def test_the_selection_is_the_reference_s_ties_included(how):
    """``top_mask`` (32 counts on an integer key, then the ties lowest index first) against the reference's
    ``lax.top_k``: scores of few distinct values (most of them tied, nought among them, negatives too);
    rows with fewer visible keys than ``k`` keep every visible one."""
    key = jax.random.PRNGKey(7)
    scores = jax.random.normal(key, (5, 40, 50))
    if how == "ties":
        scores = jnp.round(scores * 2) / 2 * (jax.random.uniform(jax.random.PRNGKey(8), (5, 40, 1)) > 0.2)
        scores = jnp.where(scores == 0, 0.0, scores)  # +0, as ``index_scores`` gives every nought
    visible = jax.random.uniform(jax.random.PRNGKey(9), (5, 40, 50)) < jnp.linspace(0.05, 1.0, 40)[None, :, None]
    for k in (1, 6, 17):
        got = decoder.top_mask(scores, visible, k)
        np.testing.assert_array_equal(got, ref.exact_top(scores, visible, k))
        np.testing.assert_array_equal(jnp.sum(got, -1), jnp.minimum(jnp.sum(visible, -1), k))


def test_an_indexer_that_scores_every_key_alike_selects_the_oldest(params):
    """With the heads' weights at nought every score is nought: the six lowest positions of the episode are selected,
    in ``step``, in ``segment`` and in the reference alike."""
    flat = jax.tree_util.tree_map_with_path(lambda path, x: jnp.zeros_like(x) if "ww" in jax.tree_util.keystr(path) else x, params)
    cfg, B, T = config(), 2, 12
    carry, past = mid_episode(flat, [10, 4])
    tokens, first = tokens_of(25, T, B), firsts(T, B, ())
    want, _, _, _, chosen, pos = reference_on(flat, past, tokens, first, carry["pos"])
    stepped = carry
    for t in range(T):
        rows = []
        stepped, lg, _ = decoder.step(flat, cfg, stepped, tokens[t], first[t], jnp.float32, rows)
        np.testing.assert_allclose(lg, want[t], **TOL)
        for layer, slots in enumerate(rows):
            for b in range(B):
                oldest = list(range(min(int(pos[t, b]) + 1, TOPK)))
                assert sorted(s for s in np.asarray(slots[b]).tolist() if s >= 0) == oldest
                assert np.flatnonzero(chosen[layer][b, t]).tolist() == oldest
    np.testing.assert_allclose(decoder.segment(flat, cfg, carry, tokens, first, jnp.float32)[0], want, **TOL)


def test_the_gradients_of_the_masked_loss_and_of_l_i_match_the_reference(params):
    """The gradient of a masked function of logits and values plus the masked mean of L_I through ``segment`` (from a
    carry, a reset inside the segment) against the reference's; the indexer's leaves take gradient from L_I alone and
    every other leaf takes none from it."""
    cfg, B, T = config(), 3, 12
    carry, past = mid_episode(params, [11, 8, 10])
    tokens, first = tokens_of(22, T, B), firsts(T, B, ((6, 1),))
    mask = (jax.random.uniform(jax.random.PRNGKey(23), (T, B)) < 0.7).astype(jnp.float32)
    masked = lambda x: jnp.sum(x * mask) / jnp.sum(mask)  # noqa: E731

    def ours(p, policy=1.0, index=1.0):
        logits, values, _, kl = decoder.segment(p, cfg, carry, tokens, first, jnp.float32, index_loss=True)
        return policy * masked(jnp.sum(jnp.sin(logits), -1) + values[..., 0] ** 2) + index * masked(kl)

    def theirs(p):
        logits, values, _, kl, _, _ = reference_on(p, past, tokens, first, carry["pos"])
        return masked(jnp.sum(jnp.sin(logits), -1) + values ** 2) + masked(kl)

    got, want = jax.grad(ours)(params), jax.grad(theirs)(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-5, err_msg=jax.tree_util.keystr(path))
    only_index = jax.grad(lambda p: ours(p, policy=0.0))(params)
    only_policy = jax.grad(lambda p: ours(p, index=0.0))(params)
    for (path, gi), gp in zip(jax.tree_util.tree_leaves_with_path(only_index), jax.tree.leaves(only_policy)):
        name = jax.tree_util.keystr(path)
        if "'index'" in name:
            assert float(jnp.abs(gi).max()) > 1e-5 and not np.asarray(gp).any(), name
        else:
            assert not np.asarray(gi).any(), name


SMALL = 16  # a key block of the segment kernels that the tiny widths' cache holds four of
LONG = 4 * SMALL


def test_the_segment_kernels_and_the_masked_product_agree():
    """The tiny sparse widths on a cache of ``LONG`` positions and kernel blocks of ``SMALL``, run twice: through
    ``ops/segment_attention.py`` (interpret mode) and through the masked product it replaces (the kernels' rule made to
    refuse every prefix).  Each run prefills two envs in two chunks (40 and 20 tokens, then 8 more each: the second
    chunk reads the first's prefix), then takes one differentiated segment of 16 tokens with a reset inside it.  The
    prefill's carry, the logits, values and L_I, and the parameters' gradient agree; only the kernel path counts the
    blocks it read (each layer: every block the envs wrote, 3 and 2)."""
    cfg, B, T = config(max_len=LONG), 2, 16
    params = decoder.init_params(cfg, jax.random.PRNGKey(0))
    tokens, first = tokens_of(31, T, B), firsts(T, B, ((5, 1),))
    weights = jax.random.normal(jax.random.PRNGKey(32), (T, B, VOCAB))
    prefill = jax.jit(lambda c, tok, n: decoder.segment(params, cfg, c, tok, jnp.zeros(tok.shape), jnp.float32, extend=True, valid=n)[3])

    def loss(p, carry):
        read = []
        logits, values, _, kl = decoder.segment(p, cfg, carry, tokens, first, jnp.float32, index_loss=True, read=read)
        blocks = jnp.stack(read) if read else jnp.zeros((0,), jnp.int32)
        return jnp.sum(logits * weights) + jnp.sum(values ** 2) + jnp.sum(kl), (logits, values, kl, blocks)

    def run():
        carry = prefill(decoder.init_carry(cfg, B, jnp.float32), tokens_of(33, 40, B), jnp.asarray([40, 20]))
        carry = prefill(carry, tokens_of(34, 8, B), jnp.asarray([8, 8]))
        (_, (logits, values, kl, blocks)), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params, carry)
        return {"prefill_carry": carry, "logits": logits, "values": values, "index_loss": kl, "gradients": grads}, blocks

    with mock.patch.object(segment_attention, "BLOCK", SMALL):
        kernel, read = run()
        jax.clear_caches()  # a traced layer is cached by its function: let the masked product trace anew
        with mock.patch.object(segment_attention, "engages", lambda *args, **kwargs: False):
            masked, unread = run()
    jax.clear_caches()
    for what in kernel:
        for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(kernel[what]), jax.tree.leaves(masked[what])):
            scale = max(float(jnp.max(jnp.abs(want))), 1e-6) if what == "gradients" else 1.0
            np.testing.assert_allclose(got / scale, want / scale, **TOL, err_msg=what + jax.tree_util.keystr(path))
    assert read.tolist() == [5, 5] and unread.size == 0


def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """16 experts split 1 a share over 16 shares (the deployment's sixteen chips): the sixteen partial results add up
    to the uncut reference's layer output (no shared expert); every share routes over all 16 and counts alike."""
    sizes = dict(num_experts=16, num_experts_per_tok=4)
    whole = decoder.init_params(config(experts_held=(0, 16), **sizes), jax.random.PRNGKey(4))
    moe = whole["layer_0"]["moe"]
    m = jax.random.normal(jax.random.PRNGKey(5), (24, 64))
    want, want_counts = ref.experts_part(moe, m, dict(ref_config(experts_held=(0, 16), **sizes)), "f32")
    total = 0.0
    for first in range(16):
        cfg = config(experts_held=(first, 1), **sizes)
        experts, weights, counts = decoder.route(moe, m, cfg)
        np.testing.assert_array_equal(counts, want_counts)
        share = {k: v[first:first + 1] for k, v in moe["experts"].items()}
        total = total + decoder.held_experts(share, m, experts, weights, cfg)
    np.testing.assert_allclose(total, want, **TOL)
    assert float(jnp.abs(want).max()) > 1e-2


def test_softmax_routing_weighs_the_selected_by_a_softmax_over_their_logits():
    """``route_score: softmax``: the experts are the ``k`` largest logits, their weights a softmax over those ``k``
    logits (``p_e / sum of the selected p``), the counts the number of tokens each expert was chosen for; the update's
    bias rule leaves such a router as it is."""
    cfg = config(num_experts_per_tok=3)
    params = decoder.init_params(cfg, jax.random.PRNGKey(6))
    moe = params["layer_0"]["moe"]
    m = jax.random.normal(jax.random.PRNGKey(7), (40, 64))
    experts, weights, counts = decoder.route(moe, m, cfg)
    logits = np.asarray(m, np.float64) @ np.asarray(moe["router"], np.float64)
    top = np.argsort(-logits, axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(experts, top)
    chosen = np.take_along_axis(logits, top, axis=-1)
    soft = np.exp(chosen - chosen.max(-1, keepdims=True))
    np.testing.assert_allclose(weights, soft / soft.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_array_equal(counts, np.bincount(top.reshape(-1), minlength=8))
    assert decoder.update_router_bias(params, jnp.ones((2, 8), jnp.int32), cfg) == params


@pytest.mark.parametrize("cut", [True, False], ids=["one_chip_s_cut", "the_uncut_model"])
def test_keye_parameter_count_at_the_published_widths(cut):
    """The configuration's table (chipbench/configs/keye_vl2_30b_ep16.json) from the shapes ``init_params`` makes,
    leaf by leaf; and the uncut language model (48 layers, 128 experts, the whole vocabulary, no value head)."""
    published = model("keye_vl2")
    file = json.loads((ROOT / "chipbench/configs/keye_vl2_30b_ep16.json").read_text())
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))  # noqa: E731
    if not cut:
        whole = dict(published, layer_types=["sparse_attention"] * file["published"]["num_hidden_layers"],
                     experts_held=[0, file["published"]["num_experts"]])
        cfg = DecoderConfig.from_dict(whole, vocab_size=file["published"]["vocab_size"], max_len=32768)
        shapes = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.PRNGKey(0))
        language_model = count({k: v for k, v in shapes.items() if k != "value_head"})
        assert language_model == 30640656384
        assert language_model - 48 * count(shapes["layer_0"]["index"]) == 30532122624
        return
    cfg = DecoderConfig.from_dict(published, vocab_size=file["vocab_size"], max_len=32768)
    assert cfg.layer_types == ("sparse_attention",) * file["num_hidden_layers"] and cfg.experts_held == (0, file["num_experts"])
    shapes = jax.eval_shape(lambda k: decoder.init_params(cfg, k), jax.random.PRNGKey(0))
    assert jax.tree.map(lambda x: tuple(x.shape), shapes["layer_0"]) == {
        "norm_in": (2048,), "wq": (2048, 4096), "wk": (2048, 512), "wv": (2048, 512), "wo": (4096, 2048),
        "q_norm": (128,), "k_norm": (128,), "norm_pre_mlp": (2048,),
        "index": {"wq": (2048, 1024), "wk": (2048, 64), "norm": (64,), "norm_bias": (64,), "ww": (2048, 16)},
        "moe": {"router": (2048, 128), "experts": {"w1": (8, 2048, 768), "w3": (8, 2048, 768), "w2": (8, 768, 2048)}}}
    stated = file["parameters"]
    layer = shapes["layer_0"]
    assert count({k: layer[k] for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")}) == stated["attention, per layer"] == 18874624
    assert count(layer["index"]) == stated["indexer, per layer"] == 2261120
    assert count({k: layer[k] for k in ("norm_in", "norm_pre_mlp", "moe")}) == stated["norms, router and 8 of 128 experts, per layer"] == 38014976
    assert [count(shapes[f"layer_{i}"]) for i in range(4)] == [stated["layer"]] * 4 == [59150720] * 4
    outside = count({k: v for k, v in shapes.items() if not k.startswith("layer_")})
    assert outside == stated["embedding, head, value head, final norm"] == 77795328
    assert count(shapes) == stated["total"] == 314398208 and 16 * count(shapes) == stated["bytes at 16 a parameter (float32 weights, gradients, Adam mu and nu)"]
    assert sum(decoder.carry_bytes(cfg).values()) == 4 * 32768 * (512 + 512 + 64) * 2 + 4  # 285.2 MB an env


@pytest.mark.parametrize("bad", [dict(index_topk=0), dict(index_heads=0), dict(route_score="sparsemax")],
                         ids=["no_top_k", "no_index_heads", "unknown_score"])
def test_a_yaml_that_states_an_impossible_sparse_layer_is_refused(bad):
    with pytest.raises(ValueError):
        config(**bad)
